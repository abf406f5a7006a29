"""Decode parity: the whole-array decode against the per-span decode it
replaced.

``_ref_*`` below are the per-span forms of ``WeaverTPU._decode``,
``WeaverTPU._resolve_cross_window_duplicates``,
``scatter_window_span_stats`` and the tail of ``fleet._decode_item``,
frozen here as the reference. Every case builds random packed blocks
(windows of 1, 2 and 32 spans; NA columns of every kind; SKIP under and
over the budget; overlapping candidate ranges, so several windows claim
one out-span) and asserts the FindAssignments 6-tuple equal to the
reference's: the same dicts, in the same insertion order, with every
top-K list in the same order.

Synthetic, no device work: tier-1 on the CPU.
"""

import types

import numpy as np
import pytest

import traceweaver_tpu.algorithms.fleet as fleet
from traceweaver_tpu.algorithms import packed_layout as _layout
from traceweaver_tpu.algorithms.weaver_tpu import (
    EndpointIds,
    PackedProblem,
    WeaverTPU,
    window_index,
)
from traceweaver_tpu.spans import NA, SKIP


# ---------------------------------------------------------------------------
# the frozen reference
# ---------------------------------------------------------------------------

def _ref_window_maps(windows):
    w_of = np.concatenate(
        [np.full(hi - lo, b) for b, (lo, hi) in enumerate(windows)])
    i_of = np.concatenate([np.arange(hi - lo) for lo, hi in windows])
    pos = np.concatenate([np.arange(lo, hi) for lo, hi in windows])
    return w_of, i_of, pos


def _ref_scatter_window_span_stats(windows, not_best, feas,
                                   span_not_best, span_cands):
    if not windows:
        return
    w_of, i_of, pos = _ref_window_maps(windows)
    span_not_best[pos] = not_best[w_of, :, i_of].any(axis=1)
    span_cands[pos] = np.maximum(
        feas[w_of, :, i_of], 1).astype(np.int64).prod(axis=1)


def _ref_decode(packed, assign, topk_cols, all_assignments, all_topk):
    M = packed.M
    K = topk_cols.shape[3]
    skip_v = np.empty((), dtype=object)
    skip_v[()] = SKIP
    na_v = np.empty((), dtype=object)
    na_v[()] = NA
    w_of, i_of, pos = _ref_window_maps(packed.windows)
    if isinstance(packed.in_ids, np.ndarray):
        span_ids = packed.in_ids[pos].tolist()
    else:
        span_ids = [packed.in_ids[p] for p in pos]
    for e, ep in enumerate(packed.out_eps):
        ids = packed.out_id_array(e)
        cols = assign[w_of, e, i_of]
        chosen = ids[w_of * M + np.clip(cols, 0, M - 1)]
        chosen[chosen == None] = na_v  # noqa: E711
        chosen[cols < 0] = na_v
        chosen[cols == M] = skip_v
        tk = topk_cols[w_of, e, i_of, :]
        tk_ids = ids[w_of[:, None] * M + np.clip(tk, 0, M - 1)]
        tk_ids[tk_ids == None] = na_v  # noqa: E711
        tk_ids[(tk < 0) | (tk > M)] = na_v
        tk_ids[tk == M] = skip_v
        amap = all_assignments[ep]
        tmap = all_topk[ep]
        chosen_l = chosen.tolist()
        tk_l = tk_ids.tolist()
        for j, in_id in enumerate(span_ids):
            out_id = chosen_l[j]
            tks = tk_l[j]
            if out_id in tks:
                tks.remove(out_id)
            amap[in_id] = out_id
            tmap[in_id] = [out_id] + tks[: K - 1]


def _ref_resolve(all_assignments, all_topk, in_ids, skip_budget):
    for ep, assign_map in all_assignments.items():
        claims = {}
        skips_used = 0
        for in_id in in_ids:
            out_id = assign_map.get(in_id)
            if out_id == SKIP:
                skips_used += 1
            elif out_id is not None and out_id != NA:
                claims.setdefault(out_id, []).append(in_id)
        used = set(claims)
        for out_id, claimants in claims.items():
            for in_id in claimants[1:]:
                replacement = NA
                for cand in all_topk.get(ep, {}).get(in_id, []):
                    if cand == SKIP:
                        if skips_used < skip_budget.get(ep, 0):
                            replacement = SKIP
                            skips_used += 1
                            break
                        continue
                    if cand != NA and cand not in used:
                        replacement = cand
                        break
                assign_map[in_id] = replacement
                if replacement not in (NA, SKIP):
                    used.add(replacement)
                tk = all_topk.get(ep, {}).get(in_id)
                if tk and replacement in tk:
                    tk.remove(replacement)
                    tk.insert(0, replacement)


def _ref_decode_item(packed, rows, in_ids, out_eps, skip_budget):
    ch = _layout.split_packed(rows)
    n_in = len(in_ids)
    all_assignments = {ep: {} for ep in out_eps}
    all_topk = {ep: {} for ep in out_eps}
    _ref_decode(packed, ch["assign"], ch["topk_cols"], all_assignments,
                all_topk)
    span_not_best = np.zeros(n_in, dtype=bool)
    span_cands = np.ones(n_in, dtype=np.int64)
    _ref_scatter_window_span_stats(packed.windows, ch["not_best"],
                                   ch["feas"], span_not_best, span_cands)
    _ref_resolve(all_assignments, all_topk, in_ids, skip_budget)
    cnt_unassigned = sum(
        1 for in_id in in_ids
        if any(all_assignments[ep][in_id] == NA for ep in out_eps))
    return (all_assignments, all_topk, int(span_not_best.sum()), n_in,
            {in_ids[j]: int(span_cands[j]) for j in range(n_in)},
            cnt_unassigned)


# ---------------------------------------------------------------------------
# random packed blocks
# ---------------------------------------------------------------------------

def _columns(rng, count, M, n):
    """n raw device columns for a window row with ``count`` candidates:
    mostly real, then an empty slot, a negative, one past M, SKIP."""
    kind = rng.choice(5, size=n, p=[0.70, 0.08, 0.08, 0.06, 0.08])
    real = rng.integers(0, max(count, 1), size=n)
    empty = rng.integers(min(count, M - 1), M, size=n)
    neg = rng.choice([-1, -3], size=n)
    over = rng.integers(M + 1, M + 4, size=n)
    return np.choose(kind, [real, empty, neg, over, np.full(n, M)])


def _batch(rng, sizes, n_windows, E, M, K, columnar, n_in0=0,
           tables=None):
    """One packed batch over spans ``n_in0..``: windows tile them, every
    endpoint's candidate ranges overlap across windows (small tables),
    so several windows claim the same out-span."""
    lens = rng.choice(sizes, size=n_windows)
    his = n_in0 + np.cumsum(lens)
    windows = [(int(h - n), int(h)) for h, n in zip(his, lens)]
    B, W = n_windows, int(max(lens))
    if tables is None:
        tables = []
        for e in range(E):
            table = np.empty(max(4, n_windows // 2 + M), dtype=object)
            table[:] = [("o", f"{e}:{k}") for k in range(len(table))]
            tables.append(table)
    out_ids, counts = [], []
    for e in range(E):
        table = tables[e]
        count = rng.integers(0, M + 1, size=B)
        counts.append(count)
        r0 = rng.integers(0, len(table) - M + 1, size=B)
        if columnar:
            out_ids.append(EndpointIds(table, r0, count, M))
        else:
            col = [None] * (B * M)
            for b in range(B):
                for j in range(int(count[b])):
                    col[b * M + j] = table[r0[b] + j]
            out_ids.append(col)
    counts = np.stack(counts, axis=1)                        # [B, E]
    block = np.zeros((B, E, W, _layout.n_channels(K)), dtype=np.int32)
    for b in range(B):
        for e in range(E):
            c = int(counts[b, e])
            block[b, e, :, _layout.CH_ASSIGN] = _columns(rng, c, M, W)
            tk = _columns(rng, c, M, W * K).reshape(W, K)
            # the chosen column among its own top-K, often twice
            dup = rng.random((W, K)) < 0.25
            tk = np.where(dup, block[b, e, :, _layout.CH_ASSIGN][:, None],
                          tk)
            block[b, e, :, _layout.CH_TOPK:] = tk
    block[..., _layout.CH_NOT_BEST] = rng.integers(0, 2, size=(B, E, W))
    block[..., _layout.CH_FEAS] = rng.integers(0, M + 1, size=(B, E, W))
    out_eps = [f"svc{e}:op" for e in range(E)]
    return block, windows, out_ids, out_eps, tables


def _packed(block, windows, out_ids, out_eps, in_ids, M):
    B, E = block.shape[:2]
    return PackedProblem(
        arrays={"out_start": np.zeros((B, E, M), dtype=np.float32)},
        out_eps=out_eps, windows=windows, in_ids=in_ids, out_ids=out_ids,
        n_in=len(in_ids))


def _in_ids(n, columnar):
    ids = [("t", f"in{p}") for p in range(n)]
    if not columnar:
        return ids
    arr = np.empty(n, dtype=object)
    arr[:] = ids
    return arr


def _assert_same(got, ref):
    assert got[2:] == ref[2:]
    for new_maps, ref_maps in zip(got[:2], ref[:2]):
        assert list(new_maps) == list(ref_maps)
        for ep in ref_maps:
            assert list(new_maps[ep].items()) == list(ref_maps[ep].items())


CASES = {
    # name: (window sizes, windows, E, M, K, columnar, skip budget)
    "singles-columnar": ((1,), 300, 2, 8, 5, True, 0),
    "pairs-columnar": ((1, 2), 200, 3, 8, 5, True, 3),
    "wide-columnar": ((32,), 12, 2, 32, 5, True, 10 ** 6),
    "mixed-columnar-k1": ((1, 2, 32), 40, 2, 16, 1, True, 2),
    "singles-objects": ((1,), 200, 2, 8, 5, False, 0),
    "mixed-objects": ((1, 2, 32), 30, 3, 16, 4, False, 10 ** 6),
}


@pytest.mark.parametrize("case", sorted(CASES) + ["two-batches-columnar",
                                                  "two-batches-objects"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_decode_matches_per_span_reference(case, seed):
    rng = np.random.default_rng(seed)
    if case.startswith("two-batches"):
        # the per-service path: two batches fill one set of dicts
        columnar = case.endswith("columnar")
        M, K = 8, 5
        b1 = _batch(rng, (1, 2), 60, 2, M, K, columnar)
        n1 = b1[1][-1][1]
        b2 = _batch(rng, (1, 32), 10, 2, M, K, columnar, n_in0=n1,
                    tables=b1[4])
        n_in = b2[1][-1][1]
        in_ids = _in_ids(n_in, columnar)
        ids_l = list(in_ids)
        budget = {b1[3][0]: 2, b1[3][1]: 10 ** 6}
        batches = []
        for block, windows, out_ids, out_eps, _ in (b1, b2):
            ch = _layout.split_packed(block)
            batches.append((_packed(block, windows, out_ids, out_eps,
                                    in_ids, M),
                            (ch["assign"], ch["topk_cols"], ch["not_best"],
                             ch["feas"])))
        out_eps = b1[3]
        maps, topk, not_best, cands, n_contested = WeaverTPU._decode_batches(
            batches, out_eps, ids_l, budget)
        got = (maps, topk, int(not_best.sum()), n_in,
               dict(zip(ids_l, cands.tolist())))
        ref_maps = {ep: {} for ep in out_eps}
        ref_topk = {ep: {} for ep in out_eps}
        ref_nb = np.zeros(n_in, dtype=bool)
        ref_cands = np.ones(n_in, dtype=np.int64)
        for packed, (assign, topk_cols, nb, feas) in batches:
            _ref_decode(packed, assign, topk_cols, ref_maps, ref_topk)
            _ref_scatter_window_span_stats(packed.windows, nb, feas,
                                           ref_nb, ref_cands)
        _ref_resolve(ref_maps, ref_topk, ids_l, budget)
        ref = (ref_maps, ref_topk, int(ref_nb.sum()), n_in,
               {ids_l[j]: int(ref_cands[j]) for j in range(n_in)})
        _assert_same(got, ref)
        assert n_contested > 0
        return

    sizes, n_windows, E, M, K, columnar, budget = CASES[case]
    block, windows, out_ids, out_eps, _ = _batch(rng, sizes, n_windows,
                                                 E, M, K, columnar)
    n_in = windows[-1][1]
    in_ids = _in_ids(n_in, columnar)
    ids_l = list(in_ids)
    skip_budget = {ep: budget for ep in out_eps}
    packed = _packed(block, windows, out_ids, out_eps, in_ids, M)
    ref = _ref_decode_item(packed, block, ids_l, out_eps, skip_budget)

    prep = dict(out_eps=out_eps, n_in=n_in, skip_budget=skip_budget)
    if columnar:
        prep["in_cols"] = types.SimpleNamespace(ids=in_ids)
    else:
        prep["in_spans"] = [types.SimpleNamespace(GetId=lambda i=i: i)
                            for i in ids_l]
    stats = {}
    got = fleet._decode_item(WeaverTPU, types.SimpleNamespace(tenant=None),
                             prep, packed, block, False, None, 0,
                             fleet._Stats(stats))
    _assert_same(got, ref)
    # the random blocks do contest out-spans, so the serial rule runs
    assert stats["decode_contested"] > 0


def test_loser_skips_a_candidate_an_earlier_loser_took():
    """Two contested out-spans; the first loser takes o3, so the second
    loser, whose first free candidate was o3 too, falls to o4; the SKIP
    budget of one goes to nobody because neither loser ranks SKIP before
    a free span."""
    M, K = 4, 3
    table = np.empty(5, dtype=object)
    table[:] = [("o", f"o{k}") for k in range(5)]
    # windows of one span each; windows 0, 1 see o0..o3, windows 2, 3
    # see o1..o4, and each chooses its column 0: spans 0 and 1 claim o0,
    # spans 2 and 3 claim o1
    windows = [(p, p + 1) for p in range(4)]
    r0 = np.array([0, 0, 1, 1])
    count = np.full(4, 4)
    block = np.zeros((4, 1, 1, _layout.n_channels(K)), dtype=np.int32)
    block[:, 0, 0, _layout.CH_ASSIGN] = 0
    # top-K after the chosen one: o3 then SKIP (spans 0, 1), o3 then o4
    # (spans 2, 3)
    block[:, 0, 0, _layout.CH_TOPK:] = [[0, 3, M], [0, 3, M],
                                        [0, 2, 3], [0, 2, 3]]
    in_ids = _in_ids(4, True)
    ids_l = list(in_ids)
    out_eps = ["svc:op"]
    packed = _packed(block, windows, [EndpointIds(table, r0, count, M)],
                     out_eps, in_ids, M)
    budget = {"svc:op": 1}
    ref = _ref_decode_item(packed, block, ids_l, out_eps, budget)
    prep = dict(out_eps=out_eps, n_in=4, skip_budget=budget,
                in_cols=types.SimpleNamespace(ids=in_ids))
    stats = {}
    got = fleet._decode_item(WeaverTPU, types.SimpleNamespace(tenant=None),
                             prep, packed, block, False, None, 0,
                             fleet._Stats(stats))
    _assert_same(got, ref)
    amap = got[0]["svc:op"]
    assert amap[ids_l[0]] == ("o", "o0")
    assert amap[ids_l[1]] == ("o", "o3")     # first loser, first free
    assert amap[ids_l[2]] == ("o", "o1")
    assert amap[ids_l[3]] == ("o", "o4")     # o3 taken by the first loser
    assert stats["decode_contested"] == 2.0


@pytest.mark.parametrize("seed", range(4))
def test_window_index_matches_list_form(seed):
    rng = np.random.default_rng(seed)
    lens = rng.integers(0, 5, size=rng.integers(1, 60))
    lens[rng.integers(0, len(lens))] = 0     # at least one empty window
    his = np.cumsum(lens) + int(rng.integers(0, 3))
    windows = [(int(h - n), int(h)) for h, n in zip(his, lens)]
    if not lens.any():
        lens[0] = 1
        windows[0] = (windows[0][0], windows[0][0] + 1)
    got = window_index(windows)
    ref = _ref_window_maps(windows)
    for g, r in zip(got, ref):
        assert g.dtype == r.dtype
        np.testing.assert_array_equal(g, r)


@pytest.mark.parametrize("windows", [[], [(3, 3)], [(0, 0), (0, 2), (2, 2)]])
def test_window_index_empty_and_zero_length(windows):
    w_of, i_of, pos = window_index(windows)
    n = sum(hi - lo for lo, hi in windows)
    assert w_of.shape == i_of.shape == pos.shape == (n,)
    if n:
        np.testing.assert_array_equal(w_of, [1, 1])
        np.testing.assert_array_equal(i_of, [0, 1])
        np.testing.assert_array_equal(pos, [0, 1])
