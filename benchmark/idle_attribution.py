"""Device idle time by the host span the program was in.

``trace.reduce_events`` gives each whole idle gap to the host span over
its midpoint, so a gap that runs across several stages goes to one of
them. This module splits the gaps instead: over the stretch of
``bench:round`` spans, per chip, each idle instant (outside the union of
device operations) goes to the innermost host span at that instant
(``idle`` where none is), then the mean over chips. Innermost is
``trace``'s own: the shortest span, the first listed among equal ones.

The program names its fleet stages ``tw:fleet:<stage>``
(``traceweaver_tpu/obs/profile.stage``) and its whole call
``tw:fleet:solve``. Idle time whose innermost span is ``bench:round`` or
``tw:fleet:solve`` is time no stage names. A trace of a program without
these spans gives nothing to read: the functions below return ``None``.
"""

from __future__ import annotations

import heapq
from typing import Dict, Iterable, List, Optional

from benchmark import trace

SOLVE_SPAN = "tw:fleet:solve"
#: innermost spans under which idle time is put down to no stage
UNNAMED = (trace.ROUND_SPAN, SOLVE_SPAN)


def innermost_pieces(spans: List[trace.Event], lo: int, hi: int
                     ) -> List[trace.Event]:
    """``[lo, hi)`` cut at every span edge, each piece labelled with the
    innermost host span over it (``idle`` where none is)."""
    cuts = sorted({lo, hi} | {t for s, e, _ in spans for t in (s, e)
                              if lo < t < hi})
    order = sorted(range(len(spans)), key=lambda i: spans[i][0])
    open_spans: List = []  # heap of (length, list index)
    pieces: List[trace.Event] = []
    j = 0
    for a, b in zip(cuts, cuts[1:]):
        while j < len(order) and spans[order[j]][0] <= a:
            s, e, _ = spans[order[j]]
            heapq.heappush(open_spans, (e - s, order[j]))
            j += 1
        while open_spans and spans[open_spans[0][1]][1] <= a:
            heapq.heappop(open_spans)
        label = spans[open_spans[0][1]][2] if open_spans else "idle"
        pieces.append((a, b, label))
    return pieces


def idle_by_label(events: Dict) -> Dict[str, float]:
    """Seconds of device idle time per innermost host label, per chip
    mean, over the traced rounds."""
    lo, hi, _ = trace._stretch(events)
    pieces = innermost_pieces(events["host"]["spans"], lo, hi)
    idle_ns: Dict[str, int] = {}
    for dev in events["devices"].values():
        busy = trace.union(trace._clip([(s, e) for s, e, _ in dev["ops"]],
                                       lo, hi))
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        k = 0
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            while pieces[k][1] <= g0:
                k += 1
            for a, b, label in pieces[k:]:
                if a >= g1:
                    break
                overlap = min(b, g1) - max(a, g0)
                if overlap > 0:
                    idle_ns[label] = idle_ns.get(label, 0) + overlap
    n_dev = len(events["devices"])
    return {label: ns / n_dev / 1e9 for label, ns in idle_ns.items()}


def _has_span(events: Dict, labels: Iterable[str]) -> bool:
    names = set(labels)
    return any(name in names for _, _, name in events["host"]["spans"])


def stage_idle_ms_per_kspan(m: Dict, stages: Iterable[str]
                            ) -> Optional[float]:
    """Device idle ms under the named fleet stages per 1000 traced
    spans; ``None`` when the trace holds none of their spans."""
    events = m.get("trace_events")
    if not events or not m.get("traced_spans"):
        return None
    labels = ["tw:fleet:" + s for s in stages]
    if not _has_span(events, labels):
        return None
    idle = idle_by_label(events)
    idle_s = sum(idle.get(label, 0.0) for label in labels)
    return 1000.0 * idle_s / (m["traced_spans"] / 1000.0)


def unnamed_idle_pct(m: Dict) -> Optional[float]:
    """Share of device idle time whose innermost span is the benchmark's
    round or the program's whole solve; ``None`` when the trace has no
    ``tw:fleet:solve`` span."""
    events = m.get("trace_events")
    if not events or not _has_span(events, [SOLVE_SPAN]):
        return None
    idle = idle_by_label(events)
    total = sum(idle.values())
    if total <= 0:
        return None
    return 100.0 * sum(idle.get(label, 0.0) for label in UNNAMED) / total
