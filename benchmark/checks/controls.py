"""Controls of the batch cells: answers that break a stated guarantee.

The configuration states its guarantees and no numeric precision (its
answer is a set of links, not numbers), so a cell's control breaks one
of its guarantees. Each cell names its control in its workload file.
A control runs on the generator's drawn calls, is graded by the same
comparison as the program (``callgraph_truth.grade``), and has to come
out as not correct.

- ``nearest_start``: the shortcut that would tempt a later change: skip
  the optimal-transport solve and its one-to-one rounding, and link each
  incoming span to the call of the same endpoint that starts first at
  or after it. Where traces overlap, two callers then share one call and
  a trace is linked to its neighbour's.
- ``swapped_links``: the true links, with the last call of the first two
  traces of every request type swapped between them. Where no traces
  overlap every time-ordered rule is right, so only a link altered where
  it is produced breaks the guarantee.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Tuple

import numpy as np


def swapped_links(graphs: List[List[List[Tuple]]]) -> List[Tuple]:
    """The truth, with one link swapped between two traces per request type."""
    outs = []
    for g, traces in enumerate(graphs):
        assign = {}
        for calls in traces:
            for _, _, rpc, caller, _, _, _ in calls:
                if caller != "USER":
                    tid = calls[0][0]
                    assign[(tid, rpc.rsplit(".", 1)[0]), rpc] = (tid, rpc)
        (a, b) = [calls[0][0] for calls in traces[:2]]
        last = traces[0][-1][2]
        parent = last.rsplit(".", 1)[0]
        assign[(a, parent), last], assign[(b, parent), last] = \
            (b, last), (a, last)
        by_ep: Dict[str, Dict] = {}
        for (in_id, rpc), out_id in assign.items():
            by_ep.setdefault(f"{g}:{rpc}", {})[in_id] = out_id
        outs.append((by_ep,))
    return outs


def nearest_start(graphs: List[List[List[Tuple]]]) -> List[Tuple]:
    """Per (request type, call position), each trace's caller span linked to
    the first call at that position starting at or after it; returned in
    the solve's result layout (``[({endpoint: {in: out}},), ...]``)."""
    outs = []
    for g, traces in enumerate(graphs):
        tids = [calls[0][0] for calls in traces]
        starts: Dict[str, np.ndarray] = {}
        for rpc_index, call in enumerate(traces[0]):
            starts[call[2]] = np.array(
                [calls[rpc_index][1] for calls in traces], dtype=np.int64)
        for rpc, caller in zip(
                (c[2] for c in traces[0]), (c[3] for c in traces[0])):
            if caller == "USER":
                continue
            parent = rpc.rsplit(".", 1)[0]
            in_start, out_start = starts[parent], starts[rpc]
            order = np.argsort(out_start, kind="stable")
            pos = np.searchsorted(out_start[order], in_start, side="left")
            pos = np.minimum(pos, len(order) - 1)
            pick = order[pos]
            outs.append(({f"{g}:{rpc}": {
                (tids[t], parent): (tids[int(pick[t])], rpc)
                for t in range(len(tids))}},))
    return outs


CONTROLS: Dict[str, Callable] = dict(nearest_start=nearest_start,
                                     swapped_links=swapped_links)
