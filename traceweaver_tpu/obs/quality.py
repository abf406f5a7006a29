"""Reconstruction-quality telemetry: plan-derived confidence, calibration
inputs, and a ground-truth-free drift gauge (ISSUE 10, docs/OBSERVABILITY.md
"Quality telemetry").

TraceWeaver's output is *inferred*: every emitted trace is a statistical
assignment that is right only regime-dependently (PAPER.md concedes 0.36
exact-match on high-fan-out services). PR 9 made the *pipeline*
observable; this module makes the *reconstruction quality* observable —
every span's confidence is reduced from the solver's own plan outputs,
summarized onto every emitted trace (``tw.confidence``), scraped as
per-tenant histograms (``tw_trace_confidence``), and watched for
distribution shift without any ground truth (PSI drift gauge).

Two confidence tiers, both reduced HOST-SIDE from the packed solver
block (:mod:`traceweaver_tpu.algorithms.packed_layout`):

- **base** (always available, zero device change — the default device
  programs stay byte-identical): the OT-overrode-argmax flag
  (``CH_NOT_BEST``), the feasible-candidate count (``CH_FEAS``), and the
  plan's top-k SUPPORT — how many candidate columns kept plan mass
  above ``MIN_TOPK_MASS`` (non-``-1`` top-k entries). Support is a
  direct transport-plan quantity: a one-hot plan row has support 1.
  ``conf = (0.5 if overridden else 1.0) / sqrt(max support over
  endpoints)``.
- **device** (``TW_CONF_DEVICE=1`` — one extra compiled program
  variant, then zero recompiles): the quantized top1-top2 row score
  margin and the entropy of the row's entropic-OT conditional
  ``softmax(S/eps)``, exported as two trailing int32 channels.
  ``conf = (0.5 if overridden else 1.0) * (1 - exp(-margin_min))``,
  with the margin reduced over endpoints by min (the weakest link: a
  trace is exactly right only if EVERY endpoint is).

Unlike :mod:`traceweaver_tpu.obs.registry`/``events`` (import-light,
stdlib only), this module imports numpy at module scope — it is consumed
only by solver-side code (fleet decode, stream emission) where numpy is
already resident; the ``cli events``/``lint`` fast paths never import it.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from traceweaver_tpu.algorithms import packed_layout as _layout
from traceweaver_tpu.obs import events as _events
from traceweaver_tpu.obs.registry import get_registry as _get_registry
from traceweaver_tpu.runtime import knobs as _knobs

#: confidence is a probability-like score in [0, 1]; bucket edges chosen
#: so the low tail (the traces an operator should distrust) is resolved
CONF_BUCKETS = (0.05, 0.1, 0.2, 0.3, 0.5, 0.7, 0.9, 0.99, 1.0)

_OBS = _get_registry()
_OBS_TRACE_CONF = _OBS.histogram(
    "tw_trace_confidence",
    "per-emitted-trace reconstruction confidence (min over the trace's "
    "solved spans; docs/OBSERVABILITY.md Quality telemetry)",
    labels=("tenant",), buckets=CONF_BUCKETS)
_OBS_LOW_CONF = _OBS.counter(
    "tw_low_confidence_traces_total",
    "emitted traces whose confidence fell below TW_CONF_LOW",
    labels=("tenant",))
_OBS_DRIFT = _OBS.gauge(
    "tw_confidence_drift_psi",
    "PSI shift statistic of the rolling per-service confidence "
    "distribution vs its frozen reference window (ground-truth-free "
    "drift signal)",
    labels=("key",))
_OBS_DRIFT_MATURE = _OBS.gauge(
    "tw_confidence_drift_mature",
    "1 once the rolling confidence window behind tw_confidence_drift_psi "
    "is fully populated, 0 while the PSI is estimated from a thin "
    "window (sampling noise, not drift — the adapt ladder ignores "
    "immature PSI, and a dashboard should too: CAMPAIGN_r18's "
    "psi=6.17 excursion was an immature chaos-phase window)",
    labels=("key",))


def conf_enabled() -> bool:
    """``TW_CONFIDENCE=0`` kills the whole quality path (no per-span
    reductions, no ``tw.confidence`` on emitted records). Read at call
    time like every knob."""
    return _knobs.get_bool("TW_CONFIDENCE")


def conf_device_enabled() -> bool:
    """``TW_CONF_DEVICE=1`` opts the fleet dispatches into the
    confidence program variant (margin/entropy channels). A static jit
    arg: one compile for the new variant, zero recompiles after."""
    return _knobs.get_bool("TW_CONF_DEVICE")


def low_threshold() -> float:
    """``TW_CONF_LOW``: traces at or below this confidence count as
    low-confidence (counter + query surface default)."""
    return _knobs.get_float("TW_CONF_LOW")


# ---------------------------------------------------------------------------
# per-span reductions over a packed window batch (host side, vectorized)
# ---------------------------------------------------------------------------

def new_span_arrays(n_in: int, device: bool = False) -> Dict[str, np.ndarray]:
    """Preallocated per-span quality arrays a caller scatters batches
    into (:func:`scatter_confidence`) before :func:`finish_confidence`."""
    out: Dict[str, np.ndarray] = dict(
        not_best=np.zeros(n_in, dtype=bool),
        cands=np.ones(n_in, dtype=np.int64),
        support=np.ones(n_in, dtype=np.int32),
    )
    if device:
        out["margin"] = np.zeros(n_in, dtype=np.float64)
        out["entropy"] = np.zeros(n_in, dtype=np.float64)
    return out


def scatter_confidence(index: Tuple[np.ndarray, np.ndarray, np.ndarray],
                       not_best: np.ndarray, feas: np.ndarray,
                       topk_cols: np.ndarray,
                       arrs: Dict[str, np.ndarray],
                       margin_q: Optional[np.ndarray] = None,
                       entropy_q: Optional[np.ndarray] = None) -> None:
    """Scatter one packed batch's per-span quality reductions into
    ``arrs`` (in place, at the windows' span positions). Vectorized over
    the batch's ``(w_of, i_of, pos)`` index
    (:func:`traceweaver_tpu.algorithms.weaver_tpu.window_index`) —
    decode sits on the dispatch pipeline's critical path, so per-span
    Python here would gate the solve exactly like the pack loops the
    columnar path killed.

    Endpoint reductions are weakest-link by construction — a span is
    exactly right only if EVERY endpoint is: override = any, candidate
    count = product, support = max, margin = min, entropy = max.
    """
    w_of, i_of, pos = index
    arrs["not_best"][pos] = not_best[w_of, :, i_of].any(axis=1)
    arrs["cands"][pos] = np.maximum(
        feas[w_of, :, i_of], 1).astype(np.int64).prod(axis=1)
    # plan support: top-k entries below MIN_TOPK_MASS come back -1, so
    # the non-negative count per row IS the plan's credible-alternative
    # count for that endpoint
    tk = topk_cols[w_of, :, i_of, :]                     # [n, E, K]
    arrs["support"][pos] = np.maximum((tk >= 0).sum(axis=2), 1).max(axis=1)
    if margin_q is not None:
        scale = _layout.CONF_SCALE
        arrs["margin"][pos] = margin_q[w_of, :, i_of].min(axis=1) / scale
        arrs["entropy"][pos] = entropy_q[w_of, :, i_of].max(axis=1) / scale


def finish_confidence(arrs: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    arrs["conf"] = confidence_scores(arrs)
    return arrs


def span_confidence_arrays(index: Tuple[np.ndarray, np.ndarray, np.ndarray],
                           block: np.ndarray, n_in: int,
                           device: bool = False) -> Dict[str, np.ndarray]:
    """Per-span quality arrays reduced from one packed window batch.

    ``block`` is the ``[B, E, W, C]`` packed solver output
    (:mod:`traceweaver_tpu.algorithms.packed_layout`); ``index`` is the
    batch's ``(w_of, i_of, pos)`` window index, whose windows tile
    [0, n_in). Returns ``{"not_best", "cands", "support",
    "conf"[, "margin", "entropy"]}`` arrays of length ``n_in``.
    """
    ch = _layout.split_packed(block, confidence=device)
    arrs = new_span_arrays(n_in, device=device)
    scatter_confidence(index, ch["not_best"], ch["feas"],
                       ch["topk_cols"], arrs,
                       margin_q=ch.get("margin_q"),
                       entropy_q=ch.get("entropy_q"))
    return finish_confidence(arrs)


def confidence_scores(arrs: Dict[str, np.ndarray]) -> np.ndarray:
    """Map the per-span quality arrays to one score in [0, 1].

    Monotone by construction in every input the solver exports: an OT
    override halves it; more credible plan alternatives (base tier) or a
    thinner top1-top2 margin (device tier) shrink it. The *absolute*
    value is a ranking score — the scorecard's confidence-decile
    calibration table (``metrics/accuracy.py``) is what ties it to
    accuracy, per regime, with ground truth.
    """
    base = np.where(arrs["not_best"], 0.5, 1.0)
    if arrs.get("margin") is not None:
        conf = base * (1.0 - np.exp(-np.maximum(arrs["margin"], 0.0)))
    else:
        conf = base / np.sqrt(np.maximum(arrs["support"], 1))
    return np.clip(conf, 0.0, 1.0)


def confidence_records(in_ids: Sequence, arrs: Dict[str, np.ndarray],
                       ) -> Dict[object, Dict]:
    """``{span id: record}`` for one solved item. Records are plain
    JSON-serializable dicts (they ride emitted-trace records and tenant
    checkpoints)."""
    n = len(in_ids)
    conf = arrs["conf"]
    recs = {}
    has_margin = arrs.get("margin") is not None
    for j in range(n):
        rec = dict(conf=round(float(conf[j]), 4),
                   not_best=bool(arrs["not_best"][j]),
                   cands=int(arrs["cands"][j]),
                   support=int(arrs["support"][j]))
        if has_margin:
            rec["margin"] = round(float(arrs["margin"][j]), 3)
            rec["entropy"] = round(float(arrs["entropy"][j]), 3)
        recs[in_ids[j]] = rec
    return recs


def zero_confidence() -> Dict:
    """The quarantined-window record: a fully failed (all-NA) window has
    zero reconstruction confidence by definition — culprit queries must
    be able to exclude it."""
    return dict(conf=0.0, not_best=True, cands=0, support=0)


# ---------------------------------------------------------------------------
# trace / window summaries (the `tw.confidence` surface)
# ---------------------------------------------------------------------------

def trace_confidence(span_ids: Sequence, conf_by_span: Dict) -> Optional[Dict]:
    """``tw.confidence`` summary of one stitched trace: min (a trace is
    right only if every span is) and mean over its SOLVED spans. None
    when none of the trace's spans carry a record (e.g. a single-span
    trace with nothing to reconstruct)."""
    vals = [conf_by_span[sid]["conf"] for sid in span_ids
            if sid in conf_by_span]
    if not vals:
        return None
    return dict(conf=round(min(vals), 4),
                mean=round(sum(vals) / len(vals), 4),
                n_scored=len(vals))


def window_confidence_summary(conf_by_span: Dict,
                              low: Optional[float] = None) -> Dict:
    """``tw.confidence`` summary of one emitted window's solved spans."""
    if low is None:
        low = low_threshold()
    vals = [r["conf"] for r in conf_by_span.values()]
    if not vals:
        return dict(n=0)
    return dict(
        n=len(vals),
        min=round(min(vals), 4),
        mean=round(sum(vals) / len(vals), 4),
        low=int(sum(v <= low for v in vals)),
        overridden=int(sum(r["not_best"] for r in conf_by_span.values())),
    )


def observe_trace(conf: float, tenant: str) -> bool:
    """Land one emitted trace's confidence on the scrape surface
    (histogram + low counter). Returns whether it counted as low."""
    _OBS_TRACE_CONF.observe(conf, tenant=tenant)
    is_low = conf <= low_threshold()
    if is_low:
        _OBS_LOW_CONF.inc(1.0, tenant=tenant)
    return is_low


# ---------------------------------------------------------------------------
# ground-truth-free drift: PSI over the rolling confidence distribution
# ---------------------------------------------------------------------------

#: PSI bin edges over [0, 1] (right-closed; the last edge catches 1.0)
PSI_EDGES = (0.2, 0.4, 0.6, 0.8, 1.0000001)
_PSI_SMOOTH = 1e-4


def psi(ref_counts: Sequence[float], cur_counts: Sequence[float]) -> float:
    """Population-stability index between two binned distributions:
    ``sum (p_cur - p_ref) * ln(p_cur / p_ref)`` with epsilon smoothing
    (the standard ground-truth-free shift statistic; >0.1 = drifting,
    >0.25 = shifted)."""
    ref_n = max(1.0, float(sum(ref_counts)))
    cur_n = max(1.0, float(sum(cur_counts)))
    total = 0.0
    for r, c in zip(ref_counts, cur_counts):
        p_ref = max(r / ref_n, _PSI_SMOOTH)
        p_cur = max(c / cur_n, _PSI_SMOOTH)
        total += (p_cur - p_ref) * math.log(p_cur / p_ref)
    return total


def _bin_counts(values: Sequence[float]) -> List[float]:
    counts = [0.0] * len(PSI_EDGES)
    for v in values:
        for i, edge in enumerate(PSI_EDGES):
            if v <= edge:
                counts[i] += 1.0
                break
    return counts


class ConfidenceDrift:
    """Rolling per-key confidence-distribution watcher.

    The first ``window`` observations per key freeze as the REFERENCE
    distribution; after that, the most recent ``window`` observations
    form the rolling current distribution and every update recomputes
    the PSI between the two. The statistic is exported as
    ``tw_confidence_drift_psi{key=...}`` and a crossing of the alert
    threshold lands ONE structured event (kind ``confidence_drift``) in
    the ``TW_EVENTS`` sink per excursion — re-armed only after the PSI
    falls back under the threshold, so a sustained shift cannot flood
    the log.

    Ground-truth-free by construction: it watches the solver's own
    confidence outputs, so a regime change in the traffic (new overlap
    pattern, a service turning high-fan-out) shows up as drift even
    though nothing can grade the assignments online.
    """

    def __init__(self, window: Optional[int] = None,
                 threshold: Optional[float] = None) -> None:
        self.window = (window if window is not None
                       else _knobs.get_int("TW_CONF_DRIFT_WINDOW"))
        self.threshold = (threshold if threshold is not None
                          else _knobs.get_float("TW_CONF_DRIFT_PSI"))
        self._ref: Dict[str, List[float]] = {}      # frozen bin counts
        self._ref_fill: Dict[str, List[float]] = {}  # values until frozen
        self._cur: Dict[str, List[float]] = {}      # rolling values
        self._alerted: Dict[str, bool] = {}
        self.alerts = 0

    def update(self, key: str, values: Sequence[float]) -> Optional[float]:
        """Fold one window's confidence values for ``key``; returns the
        current PSI once the reference is frozen, else None."""
        if not values:
            return self.last_psi(key)
        fill = self._ref_fill.get(key)
        if key not in self._ref:
            if fill is None:
                fill = self._ref_fill[key] = []
            fill.extend(float(v) for v in values)
            if len(fill) >= self.window:
                self._ref[key] = _bin_counts(fill[:self.window])
                values = fill[self.window:]
                del self._ref_fill[key]
            else:
                return None
        cur = self._cur.setdefault(key, [])
        cur.extend(float(v) for v in values)
        del cur[:-self.window]
        if not cur:
            return None
        stat = psi(self._ref[key], _bin_counts(cur))
        _OBS_DRIFT.set(stat, key=key)
        # exported alongside the PSI so a scrape can tell a real shift
        # from a thin-window excursion without knowing the window size
        _OBS_DRIFT_MATURE.set(1.0 if self.mature(key) else 0.0, key=key)
        if stat > self.threshold and not self._alerted.get(key):
            self._alerted[key] = True
            self.alerts += 1
            _events.emit("confidence_drift", "shift", key=key,
                         psi=round(stat, 4), threshold=self.threshold,
                         window=self.window)
        elif stat <= self.threshold:
            self._alerted[key] = False
        return stat

    def last_psi(self, key: str) -> Optional[float]:
        cur = self._cur.get(key)
        if key not in self._ref or not cur:
            return None
        return psi(self._ref[key], _bin_counts(cur))

    def in_excursion(self, key: str) -> bool:
        """Is ``key``'s alert currently armed (last PSI above the
        threshold, not yet re-armed)? Consumers that suspend
        amortization while a shift is in progress — the stream
        service's plan-cache gate — read this: during an excursion the
        per-window refit must keep re-teaching the carried statistics
        until the PSI falls back under the threshold."""
        return bool(self._alerted.get(key))

    def mature(self, key: str) -> bool:
        """Is the rolling current window for ``key`` fully populated?
        Right after the reference freezes, the rolling distribution is
        estimated from a handful of values and its PSI is sampling
        noise, not drift — the GAUGE still exports it (an operator can
        weigh it), but a CONSUMER that acts on excursions (the
        adaptation controller) must wait for a full window or it will
        actuate on noise and burn its hysteresis cooldown before any
        real shift arrives."""
        return (key in self._ref
                and len(self._cur.get(key, ())) >= self.window)

    # -- checkpoint plumbing (stream/serve state rides pickles) ----------
    def state(self) -> Dict:
        return dict(window=self.window, threshold=self.threshold,
                    ref=self._ref, ref_fill=self._ref_fill,
                    cur=self._cur, alerted=self._alerted,
                    alerts=self.alerts)

    @classmethod
    def from_state(cls, state: Dict) -> "ConfidenceDrift":
        d = cls(window=state["window"], threshold=state["threshold"])
        d._ref = state["ref"]
        d._ref_fill = state["ref_fill"]
        d._cur = state["cur"]
        d._alerted = state["alerted"]
        d.alerts = state["alerts"]
        return d
