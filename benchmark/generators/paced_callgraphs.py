"""Seeded call-graph corpus for the batch reconstruction cells.

The configuration names its request types, each a fixed call graph: the
calls a request makes, with the order in which each handler makes them.
It also states the timing of a call, lognormal: the callee's own time
(half before its first call, half after its last) and the network hop
each way. The traffic mix names the request rate, the share of each
request type, how many requests make one round, and the seed of its
timeline.

Requests arrive as wrk2 sends them at a constant throughput: one every
``1 / rate_per_s`` seconds. The timeline, drawn from the mix's
``timeline_seed``, orders the request types over those slots (a shuffle
of exact counts) and draws every own time and hop. The run's ``--seed``
names the traces. Every run thus solves the same windows: a timeline
sets which requests overlap, and so the shapes of the compiled dispatch
programs, which would otherwise change from seed to seed and compile
anew in set-up.

Each request type is written as ``<type>/<trace id>.json`` in the Jaeger
layout that the program's call-graph loader (``load_corpus`` fix 5)
reads: a server and a client record per non-root call, sharing the
call's ``rpc_id`` as span id. Times are integer microseconds.
:func:`due_answers` lists what a correct reconstruction must return,
from the drawn records alone.
"""

from __future__ import annotations

import json
import math
import os
import random
import shutil
from typing import Dict, List, Tuple

#: one call: (trace id, start us, rpc id, caller, callee, interface, rt us)
Call = Tuple[str, int, str, str, str, str, int]

#: the first request arrives at this epoch time, in microseconds
T_START_US = 1_600_000_000_000_000


def type_counts(traffic: Dict) -> Dict[str, int]:
    """Requests of each type in one round: exact shares, the rounding
    remainder to the largest."""
    n = int(traffic["requests_per_round"])
    mix = traffic["mix"]
    counts = {t: int(n * float(s)) for t, s in mix.items()}
    top = max(mix, key=lambda t: float(mix[t]))
    counts[top] += n - sum(counts.values())
    return counts


def _lognormal_us(rng: random.Random, spec: Dict) -> int:
    x = rng.lognormvariate(math.log(float(spec["median_us"])),
                           float(spec["sigma"]))
    return max(1, int(round(x)))


def draw_traces(config: Dict, traffic: Dict,
                seed: int) -> List[List[List[Call]]]:
    """Per request type (configuration order), per trace, its calls (root
    first, rpc-id order)."""
    rng = random.Random(int(traffic["timeline_seed"]))
    id_rng = random.Random(int(seed))
    counts = type_counts(traffic)
    slots = [t for t in sorted(counts) for _ in range(counts[t])]
    rng.shuffle(slots)
    period_us = 1e6 / float(traffic["rate_per_s"])
    own, hop = config["timing"]["own_time"], config["timing"]["hop"]
    types = [t["name"] for t in config["request_types"]]
    graphs = {t["name"]: t for t in config["request_types"]}
    children = {}
    for name, g in graphs.items():
        kids: Dict[str, List] = {}
        for call in g["calls"]:
            kids.setdefault(call[0].rpartition(".")[0], []).append(call)
        children[name] = kids
    out: Dict[str, List[List[Call]]] = {t: [] for t in types}
    for slot, name in enumerate(slots):
        tid = f"{name}_{len(out[name]):06d}_{id_rng.randrange(1 << 32):08x}"
        kids = children[name]
        calls: List[Call] = []

        def emit(rpc: str, caller: str, callee: str, interface: str,
                 start: int) -> int:
            own_us = _lognormal_us(rng, own)
            cursor = start + own_us // 2
            for kid in kids.get(rpc, ()):
                cursor += _lognormal_us(rng, hop)
                cursor += emit(*kid, cursor)
                cursor += _lognormal_us(rng, hop)
            total = cursor + own_us - own_us // 2 - start
            calls.append((tid, start, rpc, caller, callee, interface, total))
            return total

        root = graphs[name]["calls"][0]
        emit(*root, T_START_US + int(round(slot * period_us)))
        calls.sort(key=lambda c: tuple(int(p) for p in c[2].split(".")))
        out[name].append(calls)
    return [out[t] for t in types if out[t]]


def jaeger_trace(calls: List[Call]) -> Dict:
    """One trace in the loader's Jaeger layout (server + client per call)."""
    root_rpc = calls[0][2]
    spans = []
    for tid, start_us, rpc_id, caller, callee, interface, rt_us in calls:
        server = {
            "traceID": tid, "startTime": start_us, "spanID": rpc_id,
            "caller": caller, "requestType": "rpc", "callee": callee,
            "interface": interface, "duration": rt_us,
            "tags": [{"key": "span.kind", "value": "server"}],
            "references": [], "processID": callee,
        }
        if rpc_id != root_rpc:
            server["references"].append({
                "refType": "CHILD_OF", "traceID": tid,
                "spanID": rpc_id.rpartition(".")[0]})
        spans.append(server)
        if rpc_id != root_rpc:
            client = dict(server)
            client["tags"] = [{"key": "span.kind", "value": "client"}]
            client["processID"] = caller
            client["references"] = [dict(r) for r in server["references"]]
            spans.append(client)
    return {"data": [{"traceID": calls[0][0], "spans": spans}]}


def write_corpus(graphs: List[List[List[Call]]], out_root: str) -> List[str]:
    """Write every request type's traces; returns the type directories."""
    if os.path.isdir(out_root):
        shutil.rmtree(out_root)
    dirs = []
    for traces in graphs:
        d = os.path.join(out_root, traces[0][0][0].split("_")[0])
        os.makedirs(d)
        for calls in traces:
            with open(os.path.join(d, f"{calls[0][0]}.json"), "w") as f:
                json.dump(jaeger_trace(calls), f)
        dirs.append(d)
    return dirs


def due_answers(graphs: List[List[List[Call]]]) -> Dict[str, List[Tuple]]:
    """Per trace id, the (in span id, out span id) pairs a correct
    reconstruction returns: each non-root call is the caller's incoming
    span (its parent call) linked to this call's client span."""
    due: Dict[str, List[Tuple]] = {}
    for traces in graphs:
        for calls in traces:
            tid = calls[0][0]
            due[tid] = [((tid, rpc.rpartition(".")[0]), (tid, rpc))
                        for _, _, rpc, caller, _, _, _ in calls
                        if caller != "USER"]
    return due


def span_count(graphs: List[List[List[Call]]]) -> int:
    """Spans the loader ingests: a server span per call, a client span
    per non-root call."""
    return sum(2 * len(calls) - 1 for traces in graphs for calls in traces)
