"""Compare a batch solve's assignments with the generator's truth.

The reference is the generator itself: every non-root call it drew is an
answer that is due, the caller's incoming span linked to the call's
client span of the same trace (``due_answers`` of the mix's generator).
Nothing here reads the program's own ground truth or problems.

A trace counts as reconstructed when every one of its due answers is
among the returned assignments: the arithmetic of the program's
``metrics.accuracy_end_to_end`` (a trace is right only if every service
got every hop right), applied to the generator's truth.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

#: the call-graph loader names a call's client half ``<rpc_id>.client``
CLIENT_SUFFIX = ".client"


def _span_id(sid) -> Tuple:
    tid, span = sid
    if isinstance(span, str) and span.endswith(CLIENT_SUFFIX):
        span = span[:-len(CLIENT_SUFFIX)]
    return (tid, span)


def returned_pairs(outs: Iterable) -> set:
    """Every (in span id, out span id) pair of one solve's results (the
    first element of each per-service result: ``{endpoint: {in: out}}``)."""
    pairs = set()
    for out in outs:
        if out is None:
            continue
        for assign in out[0].values():
            for in_id, out_id in assign.items():
                if isinstance(out_id, list):  # top-k style: one answer only
                    out_id = out_id[0] if len(out_id) == 1 else None
                if out_id is not None:
                    pairs.add((tuple(in_id), _span_id(out_id)))
    return pairs


def grade(due: Dict[str, List[Tuple]], outs) -> Dict[str, float]:
    """Traces and answers of one solve against the truth."""
    got = returned_pairs(outs)
    answered_in = {p[0] for p in got}
    wrong_traces = 0
    wrong_answers = 0
    unanswered_traces = 0
    unanswered = set()
    n_answers = 0
    for pairs in due.values():
        bad = 0
        missing = False
        for pair in pairs:
            n_answers += 1
            if pair not in got:
                bad += 1
                if pair[0] not in answered_in:
                    missing = True
                    unanswered.add(pair[0])
        wrong_answers += bad
        wrong_traces += bool(bad)
        unanswered_traces += missing
    n_traces = len(due)
    return dict(traces=n_traces, answers=n_answers,
                wrong_traces=wrong_traces, wrong_answers=wrong_answers,
                unanswered_traces=unanswered_traces,
                unanswered_in_spans=len(unanswered),
                wrong_trace_pct=100.0 * wrong_traces / max(1, n_traces),
                trace_accuracy_pct=100.0 * (n_traces - wrong_traces)
                / max(1, n_traces))
