"""Reduce a JAX profiler trace (``*.xplane.pb``) to device metrics.

Two steps, so that the second can be checked on a recorded trace:

1. :func:`load_events` reads the trace with ``jax.profiler.ProfileData``
   and keeps lists of ``(start_ns, end_ns, label)``: each TPU's XLA
   operations, and the host's named spans (the benchmark's ``bench:*``
   spans and the program's ``tw:*`` spans, which ``TW_PROFILE=1`` turns
   on). A trace with no TPU plane is an
   error: a host thread is never read as the device.
2. :func:`reduce_events` takes the stretch between the first and the
   last ``bench:round`` span and computes, per chip and then averaged:
   busy time as the *union* of operation intervals (overlapping or
   nested events count once), the idle gaps between them, each gap
   attributed to the innermost host span that covers its midpoint, and
   the operations that took most self time (an op's time less the ops
   nested in it, such as a ``while`` loop's body). :func:`selected_s`
   gives the time of the operations a metric's own matcher selects.
"""

from __future__ import annotations

import glob
import os
from typing import Callable, Dict, List, Tuple

Event = Tuple[int, int, str]

ROUND_SPAN = "bench:round"
HOST_PREFIXES = ("bench:", "tw:")


def op_label(name: str) -> str:
    """A device op's HLO instruction name without its numeric suffix:
    ``%sinkhorn_log_pallas.7 = f32[...] custom-call(...)`` reads
    ``sinkhorn_log_pallas``; the XLA op line names events by their whole
    instruction text."""
    head = name.split(" = ", 1)[0].lstrip("%")
    stem, _, suffix = head.rpartition(".")
    return stem if stem and suffix.isdigit() else head


def latest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load_events(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """Device ops per TPU plane, and the host's named spans."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    devices: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in data.planes:
        pname = plane.name or ""
        if pname.startswith("/device:TPU:"):
            ops: List[Event] = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    start = int(ev.start_ns)
                    ops.append((start, start + int(ev.duration_ns),
                                op_label(ev.name or "")))
            devices[pname] = dict(ops=ops)
        elif pname.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name or ""
                    if name.startswith(HOST_PREFIXES):
                        start = int(ev.start_ns)
                        host.append((start, start + int(ev.duration_ns),
                                     name))
    if not devices:
        raise ValueError(f"{path}: no /device:TPU plane; the trace holds "
                         "no device time")
    return dict(devices=devices, host={"spans": host})


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge overlapping or touching intervals."""
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _host_label(spans: List[Event], t: int) -> str:
    """Innermost host span covering time ``t`` (none: ``idle``)."""
    best = None
    for s, e, name in spans:
        if s <= t < e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "idle"


def _stretch(events: Dict) -> Tuple[int, int, int]:
    """First start and last end of the ``bench:round`` spans, and their
    count."""
    rounds = [(s, e) for s, e, name in events["host"]["spans"]
              if name == ROUND_SPAN]
    if not rounds:
        raise ValueError(f"no {ROUND_SPAN!r} span in the trace")
    return min(s for s, _ in rounds), max(e for _, e in rounds), len(rounds)


def _inside(dev: Dict, lo: int, hi: int) -> List[Event]:
    return [ev for ev in dev["ops"] if ev[1] > lo and ev[0] < hi]


def self_times(events: List[Event]) -> Dict[str, int]:
    """Per label, the ns of its events not covered by events nested in
    them (events on one line nest or follow each other)."""
    out: Dict[str, int] = {}
    stack: List[List] = []  # [end, label, self_ns]

    def close(top) -> None:
        out[top[1]] = out.get(top[1], 0) + max(0, top[2])

    for s, e, label in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, label, e - s])
    while stack:
        close(stack.pop())
    return out


def selected_s(events: Dict, match: Callable[[str], bool]) -> float:
    """Device seconds (union, per chip mean) of the operations whose
    label ``match`` selects, over the traced rounds."""
    lo, hi, _ = _stretch(events)
    total = 0
    for dev in events["devices"].values():
        sel = [(s, e) for s, e, label in _inside(dev, lo, hi)
               if match(label)]
        total += sum(e - s for s, e in union(_clip(sel, lo, hi)))
    return total / len(events["devices"]) / 1e9


def reduce_events(events: Dict, top: int = 10) -> Dict:
    """Busy and idle time over the traced rounds, per chip mean."""
    spans = events["host"]["spans"]
    lo, hi, n_rounds = _stretch(events)
    busy_ns: List[int] = []
    op_ns: Dict[str, int] = {}
    gap_ns: Dict[str, int] = {}
    for dev in events["devices"].values():
        inside = [(max(s, lo), min(e, hi), label)
                  for s, e, label in _inside(dev, lo, hi)]
        busy = union([(s, e) for s, e, _ in inside])
        busy_ns.append(sum(e - s for s, e in busy))
        for label, ns in self_times(inside).items():
            op_ns[label] = op_ns.get(label, 0) + ns
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                label = _host_label(spans, (g0 + g1) // 2)
                gap_ns[label] = gap_ns.get(label, 0) + g1 - g0
    n_dev = len(events["devices"])
    top_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]
    top_gaps = sorted(gap_ns.items(), key=lambda kv: -kv[1])[:top]
    return dict(
        window_s=(hi - lo) / 1e9,
        busy_s=sum(busy_ns) / n_dev / 1e9,
        rounds=n_rounds,
        chips=n_dev,
        device_ops=[[name, ns / n_dev / 1e9] for name, ns in top_ops],
        idle_gaps=[[name, ns / n_dev / 1e9] for name, ns in top_gaps],
    )
