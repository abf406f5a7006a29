"""The flagship TPU solver: windowed Sinkhorn assignment over score tensors.

This replaces the reference TraceWeaverV3 stack — per-span DFS candidate
enumeration (traceweaver_v3.py:292-351), top-K heaps, and a per-window
maximum-weight-independent-set ILP solved by Gurobi
(traceweaver_v3.py:1395-1419) — with a dense, branch-free formulation that
maps onto TPU vector units:

1. **Perfect-cut windowing** (host): incoming spans are segmented wherever
   the running max of end-times clears the next start — candidate sets of
   different segments are provably disjoint (the tensor analogue of
   traceweaver_v3.py:1020-1078 ``CreateWindows2``/``PerfectCut``) — then
   capped to a maximum window size and padded to a common width.
2. **Masked score tensors** (device): for each window and each outgoing
   endpoint in invocation-DAG topological order, a score matrix
   ``S[i, j] = log p(delay)`` under the learnt per-edge mixture, masked by
   timing containment and DAG-precedence feasibility (replacing the DFS
   pruning rules, traceweaver_v3.py:315-351).
3. **Entropic OT**: a Sinkhorn solve per (window, endpoint) with a
   budgeted *skip column* (capacity = the window's |in|-|out| slack,
   reference skip-budget semantics traceweaver_v3.py:972) and a dummy row
   absorbing unused columns. One-to-one conflicts are resolved by transport
   marginals instead of an independent-set ILP.
4. **Greedy peel rounding** to hard assignments; DAG consistency by
   sequential conditioning: each endpoint's chosen completion times feed
   the successor endpoints' score matrices inside one ``lax.scan``
   (replacing ``ScoreAssignmentAsPerInvocationGraph``,
   traceweaver_v1.py:259-361).
5. **EM iteration**: after a full pass, per-edge delay GMMs are refit from
   the assignments (BIC 1..5 components, traceweaver_v3.py:706-818) and the
   solve repeats (traceweaver_v3.py:1152-1229).

Everything between (2) and (4) is jitted and vmapped over windows; the
window axis is the sharding axis for multi-device runs
(see :mod:`traceweaver_tpu.parallel.mesh`).
"""

from __future__ import annotations

import math
import warnings
from dataclasses import dataclass, field
from functools import partial
from itertools import chain
from typing import Dict, List, Optional, Tuple

import numpy as np

# the packed entry points donate their window tensors (HBM-peak buffers);
# backends without aliasing support fall back to a copy and warn per call —
# pure noise at per-chunk dispatch rates on the CPU stand-in
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")

import jax
import jax.numpy as jnp
import networkx as nx

from traceweaver_tpu.algorithms import packed_layout as _layout
from traceweaver_tpu.algorithms import timing
from traceweaver_tpu.algorithms.skips import water_fill_skip_caps
from traceweaver_tpu.algorithms.timing import MAX_COMPONENTS, EdgeDist
from traceweaver_tpu.metrics.accuracy import get_out_eps_in_order
from traceweaver_tpu.obs import profile as _obs_profile
from traceweaver_tpu.obs import quality as _quality
from traceweaver_tpu.obs.registry import get_registry as _get_registry
from traceweaver_tpu.ops.pallas_sinkhorn import (
    assign_topk,
    mesh_allows_pallas,
)
from traceweaver_tpu.ops.precision import (
    precision_from_env,
    score_itemsize,
    validate_precision,
)
from traceweaver_tpu.ops.scores import mixture_logpdf, pair_scores
from traceweaver_tpu.runtime import knobs as _knobs
from traceweaver_tpu.runtime.bucketing import pow2_bucket
from traceweaver_tpu.spans import NA, SKIP, Span, SpanArray

NEG = -1.0e9
SKIP_MARGIN = 4.0    # log-space margin a real candidate must beat to avoid skip
SKIP_FLOOR = -60.0   # skip score floor so candidate-less rows still take skip
MIN_TOPK_MASS = 1e-3  # top-K fallback candidates need at least this plan mass
# Perfect-cut segments are solved whole (global one-to-one marginals) up to
# this cap; only beyond it do we fall back to capped sub-windows, which can
# double-assign an outgoing span across the artificial boundary. 1024 keeps
# the dense [W, M] score block ≤ ~8 MB — comfortably VMEM-tileable.
DEFAULT_MAX_WINDOW = 1024
DEFAULT_TOPK = 5
# Per-dispatch element budget (~f32 elements of [B, W, M] score blocks kept
# live at once). Bounds HBM while letting one dispatch cover a whole solve:
# every dispatch pays a host round trip, so fewer, fatter dispatches win
# over per-size-class ones.
CHUNK_ELEMS = 1 << 26
# Merging a smaller window size class into the next larger one trades
# padding FLOPs for one fewer device round trip; merge while the extra
# padded area (elements) stays under this budget (~a round trip's worth of
# VPU work for this pipeline).
MERGE_ELEMS = 1 << 24

# obs mirror of the per-service solver ledger (docs/OBSERVABILITY.md):
# WeaverTPU.stats keeps its field names (executor merges, bench reads);
# every accumulating update below ALSO lands here so the scrape surface
# covers the per-service fallback/baseline path, not just the fleet.
_OBS_SOLVER = _get_registry().counter(
    "tw_solver_ledger_total",
    "per-service WeaverTPU solve ledger mirror (stage seconds, "
    "analytic op/byte estimates)",
    labels=("key",))


def _stat_add(stats: Dict[str, float], key: str, val: float) -> None:
    _OBS_SOLVER.inc(val, key=key)
    stats[key] = stats.get(key, 0.0) + val


# ---------------------------------------------------------------------------
# Device solve (jit + vmap over windows)
# ---------------------------------------------------------------------------

def _solve_windows_impl(
    in_start,    # [B, W] f32 (window-rebased µs)
    in_end,      # [B, W]
    in_valid,    # [B, W] bool
    out_start,   # [B, E, M]
    out_end,     # [B, E, M]
    out_valid,   # [B, E, M] bool
    skip_cap,    # [B, E] f32 — skip-column capacity per endpoint
    force_skip,  # [B, E, W] bool — true-skips ablation; normally all False
    param_idx,   # [B] int32 — row into the stacked per-problem param tables
    pred_masks,  # [P, E, E] bool — pred[e, p]: p is a primary DAG pred of e
    root_masks,  # [P, E] bool — e additionally scored from the incoming start
    is_lasts,    # [P, E] bool — add the return-edge (e -> in) term
    edge_wts, edge_mus, edge_sds,  # [P, E, E, K] mixture params for (p -> e)
    in_wts, in_mus, in_sds,        # [P, E, K] params for (in -> e)
    ret_wts, ret_mus, ret_sds,     # [P, E, K] params for (e -> in)
    epsilon: float,
    n_sinkhorn: int,
    topk: int,
    n_sweeps: int,
    sinkhorn_tol: float,
    max_preds: int = 0,
    max_succs: int = 0,
    precision: str = "f32",
    pallas: bool = True,
    confidence: bool = False,
):
    """Shared body of :func:`solve_windows` / :func:`solve_windows_fleet`.

    Every window carries ``param_idx`` — the row of the DAG-structure and
    distribution tables it scores against — so windows of *different
    services* batch into one device program (SURVEY §2.8: services become
    a batch dimension). The single-service entry points pass P=1 and a
    zero index vector.

    ``max_preds`` / ``max_succs`` (static; 0 = no bound) cap the number of
    DAG neighbours an endpoint's score matrix sums over: instead of
    evaluating a masked [W, M, K] mixture block for EVERY other endpoint
    (O(E^2) blocks per sweep — the dominant score-build cost in the r04
    profile), neighbour indices are gathered host-known-tight so only the
    real DAG edges (in-degree is ~1 in these call graphs) pay for
    evaluation. Identical sums: gathered entries are exactly the
    mask-true entries, padding contributes 0.0.

    ``precision`` (static; see :mod:`traceweaver_tpu.ops.precision`) is
    the score-BLOCK storage precision: the mixture terms are evaluated
    and summed in f32 (accumulation stays full-precision), then each
    endpoint's assembled OT block is stored at ``precision`` before the
    Sinkhorn loop streams it — under ``"bf16"`` the block the sweep
    ``while_loop`` re-reads every iteration is half the bytes. The
    Sinkhorn potentials, marginals, convergence test, transport plan,
    and rounding margins stay f32 throughout (``ops/sinkhorn.py`` /
    ``ops/pallas_sinkhorn.py``); ``"f32"`` compiles the historical
    program bit-identically (no cast is inserted at all).

    ``confidence`` (static; default False — the historical program, no
    trace change at all) additionally exports two quantized per-row
    quality channels derived from the assembled OT block the solver
    already holds in registers (:mod:`traceweaver_tpu.algorithms.packed_layout`):
    the top1-top2 row score margin and the entropy of the row's
    entropic-OT conditional ``softmax(S/epsilon)`` — the plan-derived
    confidence signals the quality telemetry path
    (:mod:`traceweaver_tpu.obs.quality`) reduces per span. A distinct
    static-arg program variant, so enabling it costs ONE compile and
    every later solve runs from cache (zero recompiles, test-pinned).
    """
    precision = validate_precision(precision)
    B, E, M = out_start.shape
    W = in_start.shape[1]
    POS = -NEG
    n_pred = max_preds if 0 < max_preds < E else E
    n_succ = max_succs if 0 < max_succs < E else E

    def solve_one(in_s, in_e, in_v, o_s, o_e, o_v, cap, fskip, pi):
        # this window's problem tables (one gather per table; P is tiny)
        pred_mask = pred_masks[pi]      # [E, E]
        root_mask = root_masks[pi]      # [E]
        is_last = is_lasts[pi]          # [E]
        edge_wt, edge_mu, edge_sd = edge_wts[pi], edge_mus[pi], edge_sds[pi]
        in_wt, in_mu, in_sd = in_wts[pi], in_mus[pi], in_sds[pi]
        ret_wt, ret_mu, ret_sd = ret_wts[pi], ret_mus[pi], ret_sds[pi]

        # neighbour index tables, mask-true entries first (stable argsort
        # keeps ascending endpoint order, matching the full-sum order)
        pred_idx = jnp.argsort(~pred_mask, axis=1)[:, :n_pred]      # [E, n_pred]
        pred_ok = jnp.take_along_axis(pred_mask, pred_idx, axis=1)
        succ_mask = pred_mask.T                                     # [E, E]
        succ_idx = jnp.argsort(~succ_mask, axis=1)[:, :n_succ]
        succ_ok = jnp.take_along_axis(succ_mask, succ_idx, axis=1)

        def ep_step(state, e):
            chosen_end, chosen_start, backward = state
            pmask = pred_mask[e]   # [E] — predecessors of e
            smask = pred_mask[:, e]  # [E] — successors of e

            pred_end = jnp.where(pmask[:, None], chosen_end, NEG)  # [E, W]
            t_pred = jnp.max(pred_end, axis=0)                     # [W]
            has_pred = jnp.any(pmask)
            t_prev = jnp.where(has_pred, t_pred, in_s)

            # successor starts (valid only when that successor picked a real
            # span; skip/none carry POS = no constraint)
            succ_start = jnp.where(smask[:, None], chosen_start, POS)  # [E, W]
            t_succ = jnp.min(succ_start, axis=0)                       # [W]

            # --- score matrix -------------------------------------------
            S = jnp.where(
                root_mask[e],
                pair_scores(in_s, o_s[e], in_wt[e], in_mu[e], in_sd[e]),
                jnp.zeros((W, M), dtype=in_s.dtype),
            )

            def pred_term(j):
                p = pred_idx[e, j]
                sc = pair_scores(chosen_end[p], o_s[e],
                                 edge_wt[e, p], edge_mu[e, p], edge_sd[e, p])
                return jnp.where(pred_ok[e, j], sc, 0.0)

            S = S + jnp.sum(jax.vmap(pred_term)(jnp.arange(n_pred)), axis=0)

            def succ_term(j):
                # edge (e -> u): delay succ_start_u - out_end_e
                u = succ_idx[e, j]
                delta = chosen_start[u][:, None] - o_e[e][None, :]
                sc = mixture_logpdf(delta, edge_wt[u, e], edge_mu[u, e],
                                    edge_sd[u, e])
                active = succ_ok[e, j] & backward
                ok = (chosen_start[u] < POS / 2)[:, None]
                return jnp.where(active & ok, sc, 0.0)

            S = S + jnp.sum(jax.vmap(succ_term)(jnp.arange(n_succ)), axis=0)

            ret_delta = in_e[:, None] - o_e[e][None, :]
            S = S + jnp.where(
                is_last[e],
                mixture_logpdf(ret_delta, ret_wt[e], ret_mu[e], ret_sd[e]),
                0.0,
            )

            # --- feasibility --------------------------------------------
            feas = (
                in_v[:, None]
                & o_v[e][None, :]
                & (in_s[:, None] <= o_s[e][None, :])
                & (o_e[e][None, :] <= in_e[:, None])
                & (t_prev[:, None] <= o_s[e][None, :])
                & ~fskip[e][:, None]
            )
            feas = feas & (
                ~backward | (o_e[e][None, :] <= t_succ[:, None])
            )
            S = jnp.where(feas, S, NEG)
            feas_count = jnp.sum(feas, axis=1).astype(jnp.int32)

            # --- skip column --------------------------------------------
            row_best = jnp.max(S, axis=1)
            skip_score = jnp.maximum(row_best - SKIP_MARGIN, SKIP_FLOOR)
            skip_score = jnp.where(fskip[e], 0.0, skip_score)
            skip_score = jnp.where(in_v, skip_score, NEG)
            Sfull = jnp.concatenate([S, skip_score[:, None]], axis=1)  # [W, M+1]
            if precision == "bf16":
                # store the assembled OT block at the score precision:
                # this is the array the Sinkhorn loop streams twice per
                # iteration, and the argmax below compares the SAME
                # values the solver actually ranked. f32 accumulation
                # already happened (the term sums above). Each row is
                # centered at its best feasible score BEFORE the
                # downcast: entropic OT plans are invariant to per-row
                # additive constants (they fold into the f potentials),
                # and DAG-conditioned rows carry common offsets of
                # hundreds of log units (e.g. the return-edge term) that
                # would otherwise eat bf16's ~8-bit mantissa — the
                # margins BETWEEN candidates, the part the solve must
                # resolve, sit near 0 after centering. Masked entries
                # stay at NEG (an all-infeasible row centers at 0).
                row_ref = jnp.where(row_best > NEG / 2, row_best, 0.0)
                Sfull = jnp.where(Sfull > NEG / 2,
                                  Sfull - row_ref[:, None], NEG)
                Sfull = Sfull.astype(jnp.bfloat16)

            # --- marginals (dummy row absorbs surplus columns) ----------
            # marginals stay f32 regardless of the score precision (S is
            # the f32 accumulated block; counts must be exact)
            n_rows = jnp.sum(in_v).astype(S.dtype)
            n_cols = jnp.sum(o_v[e]).astype(S.dtype)
            cap_e = jnp.maximum(cap[e], jnp.maximum(n_rows - n_cols, 0.0))
            row_marg = jnp.concatenate(
                [in_v.astype(S.dtype),
                 jnp.maximum(n_cols + cap_e - n_rows, 0.0)[None]]
            )
            col_marg = jnp.concatenate([o_v[e].astype(S.dtype), cap_e[None]])
            S_ot = jnp.concatenate(
                [Sfull, jnp.zeros((1, M + 1), dtype=Sfull.dtype)], axis=0
            )

            # Sinkhorn (the Pallas kernel on TPU) -> greedy rounding ->
            # small-k peel (the topk_peel that replaced lax.top_k's lane
            # sort — sort.47 / wrapped_reduce-window in the r05
            # profiles). Candidate
            # columns with negligible plan mass (timing-infeasible:
            # score NEG -> plan ~ 0) come back as -1 so cross-window
            # duplicate resolution can never fall back onto an
            # infeasible out-span.
            col_valid = jnp.concatenate([o_v[e], (cap_e > 0)[None]])
            assign, tk = assign_topk(
                S_ot, row_marg, col_marg, in_v, col_valid, cap_e, W,
                epsilon=epsilon, n_iters=n_sinkhorn, tol=sinkhorn_tol,
                topk=topk, min_topk_mass=MIN_TOPK_MASS,
                allow_pallas=pallas)

            # chosen completion: skip passes the predecessor time through
            real = (assign >= 0) & (assign < M)
            safe = jnp.clip(assign, 0, M - 1)
            chosen_end = chosen_end.at[e].set(
                jnp.where(real, o_e[e][safe], t_prev)
            )
            chosen_start = chosen_start.at[e].set(
                jnp.where(real, o_s[e][safe], POS)
            )

            not_best = (assign != jnp.argmax(Sfull, axis=1)) & in_v
            outs = (assign, tk.astype(jnp.int32), not_best, feas_count)
            if confidence:
                # plan-derived quality channels, from the SAME assembled
                # block the solve ranked (f32 view; under bf16 the rows
                # are already best-centered, so margins keep mantissa).
                # Quantized fixed-point so they ride the existing int32
                # packed transfer — no extra D2H stream, no f32 output.
                Sf = Sfull.astype(jnp.float32)
                top2 = jax.lax.top_k(Sf, 2)[0]               # [W, 2]
                margin = jnp.maximum(top2[:, 0] - top2[:, 1], 0.0)
                # entropic-OT row conditional: the Sinkhorn plan row is
                # softmax((S + g)/eps) up to the column potentials; the
                # unconstrained conditional softmax(S/eps) is the
                # potential-free row entropy (0 = one-hot certainty)
                logits = jnp.where(Sf > NEG / 2, Sf / epsilon, NEG)
                p = jax.nn.softmax(logits, axis=1)
                ent = -jnp.sum(jnp.where(p > 0.0,
                                         p * jnp.log(p + 1e-30), 0.0),
                               axis=1)
                scale = _layout.CONF_SCALE
                margin_q = (jnp.minimum(margin, 2.0e6) * scale).astype(
                    jnp.int32)
                ent_q = (jnp.maximum(ent, 0.0) * scale).astype(jnp.int32)
                outs = outs + (margin_q, ent_q)
            return (chosen_end, chosen_start, backward), outs

        def sweep_body(carry):
            (chosen_end, chosen_start, _), outs, sweep, _ = carry
            prev_assign = outs[0]
            state = (chosen_end, chosen_start, sweep > 0)
            state, outs = jax.lax.scan(ep_step, state, jnp.arange(E))
            # a backward sweep (sweep >= 1) that reproduces the previous
            # sweep's assignments is a Gauss-Seidel fixed point: chosen
            # start/end times are functions of the assignments, so every
            # later sweep recomputes identical outputs — exiting early
            # changes nothing (exactness, not approximation)
            changed = jnp.any(outs[0] != prev_assign) | (sweep == 0)
            # outs ride the carry (overwritten each sweep) so only the final
            # sweep's outputs are ever materialized — stacking [n_sweeps, ...]
            # then slicing would cost n_sweeps x the output memory
            return state, outs, sweep + 1, changed

        def sweep_cond(carry):
            _, _, sweep, changed = carry
            return (sweep < n_sweeps) & changed

        init_state = (
            jnp.zeros((E, W), dtype=in_s.dtype),
            jnp.full((E, W), POS, dtype=in_s.dtype),
            jnp.asarray(False),
        )
        init_outs = (
            jnp.zeros((E, W), dtype=jnp.int32),
            jnp.zeros((E, W, topk), dtype=jnp.int32),
            jnp.zeros((E, W), dtype=bool),
            jnp.zeros((E, W), dtype=jnp.int32),
        )
        if confidence:
            init_outs = init_outs + (
                jnp.zeros((E, W), dtype=jnp.int32),   # margin_q
                jnp.zeros((E, W), dtype=jnp.int32),   # entropy_q
            )
        # one traced sweep body (compile surface independent of n_sweeps)
        _, outs, _, changed = jax.lax.while_loop(
            sweep_cond, sweep_body,
            (init_state, init_outs, jnp.asarray(0, jnp.int32),
             jnp.asarray(True)))
        # converged <=> the last executed sweep reproduced its predecessor's
        # assignments, i.e. the outputs are a Gauss-Seidel fixed point that
        # no further sweep budget could change. Exported per window so the
        # host can redispatch ONLY unconverged windows with the remaining
        # sweeps (convergence compaction, algorithms/fleet.py) — under vmap
        # this whole loop runs until the SLOWEST window converges, with
        # converged windows' updates select-masked into no-ops but still
        # burning VPU cycles.
        return outs + (~changed,)

    return jax.vmap(solve_one)(
        in_start, in_end, in_valid, out_start, out_end, out_valid,
        skip_cap, force_skip, param_idx,
    )


@partial(jax.jit, static_argnames=("epsilon", "n_sinkhorn", "topk", "n_sweeps",
                                   "sinkhorn_tol", "max_preds", "max_succs",
                                   "precision", "pallas"))
def solve_windows(
    in_start, in_end, in_valid, out_start, out_end, out_valid,
    skip_cap, force_skip,
    pred_mask,   # [E, E] bool
    root_mask,   # [E] bool
    is_last,     # [E] bool
    edge_wt, edge_mu, edge_sd,  # [E, E, K]
    in_wt, in_mu, in_sd,        # [E, K]
    ret_wt, ret_mu, ret_sd,     # [E, K]
    epsilon: float = 1.0,
    n_sinkhorn: int = 40,
    topk: int = DEFAULT_TOPK,
    n_sweeps: int = 5,
    sinkhorn_tol: float = 0.0,
    max_preds: int = 0,
    max_succs: int = 0,
    precision: str = "f32",
    pallas: bool = True,
):
    """Solve every window by Gauss-Seidel coordinate descent over endpoints.

    Sweep 0 conditions each endpoint only on its DAG predecessors (forward
    pass in topological order). Later sweeps re-solve each endpoint with
    both directions fixed — predecessor completion times below, successor
    start times above — recovering the joint coupling the reference gets
    from enumerating whole assignments (traceweaver_v1.py:259-361) without
    combinatorial search.

    Returns:
      assign     [B, E, W] int32 — column index per incoming span
                 (M = skip, -1 = unassigned)
      topk_cols  [B, E, W, topk] int32 — per-endpoint candidate ranking
      not_best   [B, E, W] bool — OT choice differs from row argmax
      feas_count [B, E, W] int32 — feasible candidates per row
    """
    B = in_start.shape[0]
    assign, tk, not_best, feas, _ = _solve_windows_impl(
        in_start, in_end, in_valid, out_start, out_end, out_valid,
        skip_cap, force_skip,
        jnp.zeros((B,), dtype=jnp.int32),
        pred_mask[None], root_mask[None], is_last[None],
        edge_wt[None], edge_mu[None], edge_sd[None],
        in_wt[None], in_mu[None], in_sd[None],
        ret_wt[None], ret_mu[None], ret_sd[None],
        epsilon=epsilon, n_sinkhorn=n_sinkhorn, topk=topk,
        n_sweeps=n_sweeps, sinkhorn_tol=sinkhorn_tol,
        max_preds=max_preds, max_succs=max_succs, precision=precision,
        pallas=pallas,
    )
    return assign, tk, not_best, feas


def _pack_solver_outputs(assign, tk, not_best, feas,
                         margin_q=None, entropy_q=None):
    """The single-transfer int32 layout ``[B, E, W, 3 + topk]`` (see
    :mod:`traceweaver_tpu.algorithms.packed_layout` — the single source
    of truth for the channel indices): ``CH_ASSIGN``, ``CH_NOT_BEST``,
    ``CH_FEAS``, then the topk columns, then (confidence variant only)
    the two quantized quality channels appended at the END so every
    historical channel index is unchanged.

    The per-window sweep-convergence flag is deliberately NOT a channel
    of this block any more: the fleet entry points return it as a
    separate ``[B]`` bool array so the convergence-compaction host step
    can fetch B bytes instead of blocking on the whole packed block
    (the ``copy-start`` D2H cost the r05 profile billed at parity with
    the sweep loops themselves)."""
    chans = [assign[..., None], not_best[..., None].astype(jnp.int32),
             feas[..., None], tk]
    if margin_q is not None:
        chans += [margin_q[..., None], entropy_q[..., None]]
    return jnp.concatenate(chans, axis=-1)


@partial(jax.jit, static_argnames=("epsilon", "n_sinkhorn", "topk", "n_sweeps",
                                   "sinkhorn_tol", "max_preds", "max_succs",
                                   "precision", "pallas"),
         donate_argnums=tuple(range(8)))
def solve_windows_packed(*args, epsilon: float = 1.0, n_sinkhorn: int = 40,
                         topk: int = DEFAULT_TOPK, n_sweeps: int = 5,
                         sinkhorn_tol: float = 0.0,
                         max_preds: int = 0, max_succs: int = 0,
                         precision: str = "f32",
                         pallas: bool = True):
    """:func:`solve_windows` with the outputs packed into one int32 tensor
    ``[B, E, W, 3+topk]`` (see :func:`_pack_solver_outputs`) so a solve
    costs a single device->host transfer instead of four. The window
    tensors (args 0-7) are donated: the dense [B, E, W, M] blocks are the
    solve's HBM peak and the caller always rebuilds them per dispatch."""
    B = args[0].shape[0]
    outs = _solve_windows_impl(
        *args[:8],
        jnp.zeros((B,), dtype=jnp.int32),
        *(a[None] for a in args[8:]),
        epsilon=epsilon, n_sinkhorn=n_sinkhorn, topk=topk,
        n_sweeps=n_sweeps, sinkhorn_tol=sinkhorn_tol,
        max_preds=max_preds, max_succs=max_succs, precision=precision,
        pallas=pallas,
    )
    return _pack_solver_outputs(*outs[:4])


def em_family_samples(assign, in_start, in_end, in_valid,
                      out_start, out_end, pred_mask, root_mask):
    """Per-edge delay samples for the three production refit families,
    extracted from hard assignments — the single definition shared by the
    fused single-device EM (:func:`solve_em_packed`) and the psum'd
    multi-device EM (:func:`traceweaver_tpu.parallel.mesh.em_step_sharded`),
    mirroring the host :func:`traceweaver_tpu.algorithms.timing.refit_from_assignments`
    (reference ``ComputeEpPairDistParams5``, traceweaver_v3.py:706-818):

    - ``(in -> e)``  chosen e start − incoming start, root endpoints;
    - ``(p -> e)``   chosen e start − chosen p end, DAG-primary edges;
    - ``(e -> in)``  incoming end − chosen e end, every endpoint.

    Returns ``(samples, mask)``, both ``[E + E*E + E, B*W]`` with rows in
    that family order (edge rows ``[e, p]`` row-major).
    """
    B, E, W = assign.shape
    M = out_start.shape[2]
    safe = jnp.clip(assign, 0, M - 1)
    ch_start = jnp.take_along_axis(out_start, safe, axis=2)   # [B, E, W]
    ch_end = jnp.take_along_axis(out_end, safe, axis=2)
    real = (assign >= 0) & (assign < M) & in_valid[:, None, :]

    # structure masks may be shared ([E]/[E, E]) or per-window
    # ([B, E]/[B, E, E] — the fleet path, where windows belong to
    # different services)
    rm = (root_mask if root_mask.ndim == 2
          else jnp.broadcast_to(root_mask[None], (B, E)))
    pm = (pred_mask if pred_mask.ndim == 3
          else jnp.broadcast_to(pred_mask[None], (B, E, E)))

    d_in = ch_start - in_start[:, None, :]                    # [B, E, W]
    m_in = real & rm[:, :, None]
    d_edge = ch_start[:, :, None, :] - ch_end[:, None, :, :]  # [B, E, Ep, W]
    m_edge = (real[:, :, None, :] & real[:, None, :, :]
              & pm[:, :, :, None])
    d_ret = in_end[:, None, :] - ch_end                       # [B, E, W]
    m_ret = real

    def rows(d, m, ne):
        return (jnp.moveaxis(d, 0, -2).reshape(ne, B * W),
                jnp.moveaxis(m, 0, -2).reshape(ne, B * W))

    di, mi = rows(d_in, m_in, E)
    de, me = rows(d_edge.reshape(B, E * E, W), m_edge.reshape(B, E * E, W),
                  E * E)
    dr, mr = rows(d_ret, m_ret, E)
    return (jnp.concatenate([di, de, dr], axis=0),
            jnp.concatenate([mi, me, mr], axis=0))


@partial(jax.jit, static_argnames=("epsilon", "n_sinkhorn", "topk", "n_sweeps",
                                   "sinkhorn_tol", "max_preds", "max_succs",
                                   "precision", "pallas"),
         donate_argnums=tuple(range(8)))
def solve_em_packed(
    in_start, in_end, in_valid, out_start, out_end, out_valid,
    skip_cap, force_skip, pred_mask, root_mask, is_last,
    edge_wt, edge_mu, edge_sd, in_wt, in_mu, in_sd,
    ret_wt, ret_mu, ret_sd,
    epsilon: float = 1.0, n_sinkhorn: int = 40,
    topk: int = DEFAULT_TOPK, n_sweeps: int = 5,
    sinkhorn_tol: float = 0.0,
    max_preds: int = 0, max_succs: int = 0,
    precision: str = "f32",
    pallas: bool = True,
):
    """Both EM iterations in ONE device dispatch.

    The reference's flagship runs two passes with a host-side BIC-GMM
    refit between them (traceweaver_v3.py:1152-1229 iteration loop,
    :706-818 refit); round 2 ran the same structure with the refit as a
    separate device dispatch, leaving the refit + second solve as extra
    host round trips (~44% of the warm solve in round 2).
    Here pass 0, the three-family delay extraction, the in-graph BIC-GMM
    refit (:func:`traceweaver_tpu.ops.gmm.fit_gmm_in_graph`), and pass 1
    are one XLA program: the EM loop never leaves the device.

    Refit deviations from the host path, both documented and bounded by
    the parity harness: samples come from pass-0 per-window assignments
    (before cross-window duplicate resolution — identical unless a
    perfect-cut segment was split beyond ``max_window``), and the GMM EM
    uses the deterministic quantile init / fixed iteration count of the
    device fit rather than sklearn's k-means init.

    ``precision`` covers BOTH passes' score blocks; the delay-sample
    extraction and the in-graph BIC-GMM refit between them stay f32
    (the EM statistics are the accumulator state of this pipeline).
    """
    B, E, M = out_start.shape
    W = in_start.shape[1]
    K = in_wt.shape[1]

    assign0, _, _, _ = solve_windows(
        in_start, in_end, in_valid, out_start, out_end, out_valid,
        skip_cap, force_skip, pred_mask, root_mask, is_last,
        edge_wt, edge_mu, edge_sd, in_wt, in_mu, in_sd,
        ret_wt, ret_mu, ret_sd,
        epsilon=epsilon, n_sinkhorn=n_sinkhorn, topk=topk, n_sweeps=n_sweeps,
        sinkhorn_tol=sinkhorn_tol, max_preds=max_preds, max_succs=max_succs,
        precision=precision, pallas=pallas,
    )

    # --- M-step samples: the three production edge families --------------
    samples, smask = em_family_samples(
        assign0, in_start, in_end, in_valid, out_start, out_end,
        pred_mask, root_mask)                                 # [Ne, B*W]

    from traceweaver_tpu.ops.gmm import fit_gmm_in_graph

    prior_w = jnp.concatenate([in_wt, edge_wt.reshape(E * E, K), ret_wt])
    prior_mu = jnp.concatenate([in_mu, edge_mu.reshape(E * E, K), ret_mu])
    prior_sd = jnp.concatenate([in_sd, edge_sd.reshape(E * E, K), ret_sd])
    w, mu, sd = fit_gmm_in_graph(samples, smask, prior_w, prior_mu, prior_sd,
                                 max_k=K)

    return solve_windows_packed(
        in_start, in_end, in_valid, out_start, out_end, out_valid,
        skip_cap, force_skip, pred_mask, root_mask, is_last,
        w[E:E + E * E].reshape(E, E, K), mu[E:E + E * E].reshape(E, E, K),
        sd[E:E + E * E].reshape(E, E, K),
        w[:E], mu[:E], sd[:E],
        w[E + E * E:], mu[E + E * E:], sd[E + E * E:],
        epsilon=epsilon, n_sinkhorn=n_sinkhorn, topk=topk, n_sweeps=n_sweeps,
        sinkhorn_tol=sinkhorn_tol, max_preds=max_preds, max_succs=max_succs,
        precision=precision, pallas=pallas,
    )


@partial(jax.jit, static_argnames=("epsilon", "n_sinkhorn", "topk", "n_sweeps",
                                   "sinkhorn_tol", "max_preds", "max_succs",
                                   "precision", "pallas", "confidence"),
         donate_argnums=tuple(range(8)))
def solve_windows_fleet(
    in_start, in_end, in_valid, out_start, out_end, out_valid,
    skip_cap, force_skip, param_idx,
    pred_masks, root_masks, is_lasts,
    edge_wts, edge_mus, edge_sds, in_wts, in_mus, in_sds,
    ret_wts, ret_mus, ret_sds,
    epsilon: float = 1.0, n_sinkhorn: int = 40,
    topk: int = DEFAULT_TOPK, n_sweeps: int = 5,
    sinkhorn_tol: float = 0.0,
    max_preds: int = 0, max_succs: int = 0,
    precision: str = "f32",
    pallas: bool = True,
    confidence: bool = False,
):
    """Multi-service :func:`solve_windows` with the packed int32 output
    (window tensors donated — see :func:`solve_windows_packed`).

    ``param_idx[b]`` selects the window's problem tables from the stacked
    ``[P, ...]`` arrays; windows of every service in a fleet ride one
    device dispatch (endpoint axes padded to the fleet max — padded
    endpoints have no valid columns, assign nothing, and pass predecessor
    times through, so they cannot disturb real endpoints).

    Returns ``(packed, converged)``: the ``[B, E, W, 3+topk]`` block
    (``confidence=True`` appends the two quantized quality channels —
    :mod:`traceweaver_tpu.algorithms.packed_layout`) plus the per-window
    sweep-fixed-point flags as a SEPARATE ``[B]`` bool array, so the
    convergence-compaction host step can fetch B bytes alone while the
    packed block streams D2H asynchronously."""
    outs = _solve_windows_impl(
        in_start, in_end, in_valid, out_start, out_end, out_valid,
        skip_cap, force_skip, param_idx,
        pred_masks, root_masks, is_lasts,
        edge_wts, edge_mus, edge_sds, in_wts, in_mus, in_sds,
        ret_wts, ret_mus, ret_sds,
        epsilon=epsilon, n_sinkhorn=n_sinkhorn, topk=topk,
        n_sweeps=n_sweeps, sinkhorn_tol=sinkhorn_tol,
        max_preds=max_preds, max_succs=max_succs, precision=precision,
        pallas=pallas, confidence=confidence,
    )
    return _pack_solver_outputs(*outs[:-1]), outs[-1]


def _fleet_refit_tables(assign0, in_start, in_end, in_valid,
                        out_start, out_end, param_idx,
                        window_rows, window_valid, pred_masks, root_masks,
                        edge_wts, edge_mus, edge_sds,
                        in_wts, in_mus, in_sds, ret_wts, ret_mus, ret_sds):
    """Per-service three-family BIC-GMM refit from pass-0 assignments —
    the middle stage of :func:`solve_em_fleet`, shared with the
    standalone :func:`refit_fleet_params` dispatch the convergence-
    compacted flow uses (one definition, so the fused single program and
    the compacted multi-dispatch flow cannot drift). Returns the nine
    refit param tables reshaped to ``[P, ...]`` table layout."""
    B, E, W = assign0.shape
    P, _, K = in_wts.shape
    Ne = E + E * E + E
    Bmax = window_rows.shape[1]

    # family samples over the padded endpoint axis; per-window structure
    # masks so a window only feeds its own service's family rows
    samples, smask = em_family_samples(
        assign0, in_start, in_end, in_valid, out_start, out_end,
        pred_masks[param_idx], root_masks[param_idx])       # [Ne, B*W]

    fs = samples.reshape(Ne, B, W)[:, window_rows, :]       # [Ne, P, Bmax, W]
    fm = (smask.reshape(Ne, B, W)[:, window_rows, :]
          & window_valid[None, :, :, None])
    fleet_samples = jnp.moveaxis(fs, 1, 0).reshape(P * Ne, Bmax * W)
    fleet_mask = jnp.moveaxis(fm, 1, 0).reshape(P * Ne, Bmax * W)

    from traceweaver_tpu.ops.gmm import fit_gmm_in_graph

    prior_w = jnp.concatenate(
        [in_wts, edge_wts.reshape(P, E * E, K), ret_wts], axis=1
    ).reshape(P * Ne, K)
    prior_mu = jnp.concatenate(
        [in_mus, edge_mus.reshape(P, E * E, K), ret_mus], axis=1
    ).reshape(P * Ne, K)
    prior_sd = jnp.concatenate(
        [in_sds, edge_sds.reshape(P, E * E, K), ret_sds], axis=1
    ).reshape(P * Ne, K)
    w, mu, sd = fit_gmm_in_graph(fleet_samples, fleet_mask,
                                 prior_w, prior_mu, prior_sd, max_k=K)

    w, mu, sd = (a.reshape(P, Ne, K) for a in (w, mu, sd))
    return (
        w[:, E:E + E * E].reshape(P, E, E, K),
        mu[:, E:E + E * E].reshape(P, E, E, K),
        sd[:, E:E + E * E].reshape(P, E, E, K),
        w[:, :E], mu[:, :E], sd[:, :E],
        w[:, E + E * E:], mu[:, E + E * E:], sd[:, E + E * E:],
    )


@jax.jit
def refit_fleet_params(assign0, in_start, in_end, in_valid,
                       out_start, out_end, param_idx,
                       window_rows, window_valid, pred_masks, root_masks,
                       edge_wts, edge_mus, edge_sds,
                       in_wts, in_mus, in_sds, ret_wts, ret_mus, ret_sds):
    """Standalone refit dispatch for the convergence-compacted fleet flow
    (:mod:`traceweaver_tpu.algorithms.fleet`): the host merges pass-0
    assignments from the warm + compacted dispatches, then this single
    program produces the pass-1 tables — same nine-tuple, same math as
    the refit inside :func:`solve_em_fleet`."""
    return _fleet_refit_tables(
        assign0, in_start, in_end, in_valid, out_start, out_end,
        param_idx, window_rows, window_valid, pred_masks, root_masks,
        edge_wts, edge_mus, edge_sds,
        in_wts, in_mus, in_sds, ret_wts, ret_mus, ret_sds)


@partial(jax.jit, static_argnames=("epsilon", "n_sinkhorn", "topk", "n_sweeps",
                                   "sinkhorn_tol", "max_preds", "max_succs",
                                   "precision", "pallas", "confidence"),
         donate_argnums=tuple(range(8)))
def solve_em_fleet(
    in_start, in_end, in_valid, out_start, out_end, out_valid,
    skip_cap, force_skip, param_idx, window_rows, window_valid,
    pred_masks, root_masks, is_lasts,
    edge_wts, edge_mus, edge_sds, in_wts, in_mus, in_sds,
    ret_wts, ret_mus, ret_sds,
    epsilon: float = 1.0, n_sinkhorn: int = 40,
    topk: int = DEFAULT_TOPK, n_sweeps: int = 5,
    sinkhorn_tol: float = 0.0,
    max_preds: int = 0, max_succs: int = 0,
    precision: str = "f32",
    pallas: bool = True,
    confidence: bool = False,
):
    """Both EM iterations for a whole service fleet in ONE dispatch.

    The fleet analogue of :func:`solve_em_packed`: pass 0 over every
    service's windows, per-service three-family delay extraction, one
    batched BIC-GMM refit over the ``P*Ne`` family rows, then pass 1 —
    the whole bench workload's EM never leaves the device and costs a
    single host round trip. Returns ``(packed, converged)``
    like :func:`solve_windows_fleet` (the flags are pass 1's).

    ``window_rows``/``window_valid`` ([P, Bmax] int32/bool) list each
    service's window rows in the fleet batch (the packer emits services as
    contiguous row blocks). The per-service refit matrix is built by
    GATHERING those rows — ``[P*Ne, Bmax*W]`` — rather than broadcasting
    the full sample matrix per service (``[P*Ne, B*W]``): the window axis
    a service's EM sees shrinks from the whole fleet's to its own, so the
    refit block stays ~P× smaller and scales to exp5-size fleets.
    """
    assign0, _, _, _, _ = _solve_windows_impl(
        in_start, in_end, in_valid, out_start, out_end, out_valid,
        skip_cap, force_skip, param_idx,
        pred_masks, root_masks, is_lasts,
        edge_wts, edge_mus, edge_sds, in_wts, in_mus, in_sds,
        ret_wts, ret_mus, ret_sds,
        epsilon=epsilon, n_sinkhorn=n_sinkhorn, topk=topk,
        n_sweeps=n_sweeps, sinkhorn_tol=sinkhorn_tol,
        max_preds=max_preds, max_succs=max_succs, precision=precision,
        pallas=pallas,
    )

    tables = _fleet_refit_tables(
        assign0, in_start, in_end, in_valid, out_start, out_end,
        param_idx, window_rows, window_valid, pred_masks, root_masks,
        edge_wts, edge_mus, edge_sds,
        in_wts, in_mus, in_sds, ret_wts, ret_mus, ret_sds)
    return solve_windows_fleet(
        in_start, in_end, in_valid, out_start, out_end, out_valid,
        skip_cap, force_skip, param_idx,
        pred_masks, root_masks, is_lasts,
        *tables,
        epsilon=epsilon, n_sinkhorn=n_sinkhorn, topk=topk,
        n_sweeps=n_sweeps, sinkhorn_tol=sinkhorn_tol,
        max_preds=max_preds, max_succs=max_succs, precision=precision,
        pallas=pallas, confidence=confidence,
    )


# ---------------------------------------------------------------------------
# Host-side problem packing
# ---------------------------------------------------------------------------

def columnar_enabled() -> bool:
    """``TW_COLUMNAR=0`` kills the columnar host pack path, restoring the
    per-span object walk (the bit-identical pre-columnar flow — kept as
    the kill switch and the golden-parity reference). Read at call time,
    same discipline as every other knob."""
    return _knobs.get_bool("TW_COLUMNAR")


def in_columns(in_spans: List[Span]) -> SpanArray:
    """Columns of a sorted incoming partition (one O(n) conversion — the
    ingest → solver boundary; everything after is array work)."""
    return SpanArray.from_spans(in_spans)


def out_columns(out_span_partitions: Dict[str, List[Span]],
                out_eps: List[str]) -> Dict[str, SpanArray]:
    """Ascending-start columns per outgoing endpoint — the exact
    permutation of the object path's ``sorted(spans, key=s.start_mus)``
    (stable), so candidate slices and id-table gathers line up with the
    object path element for element."""
    return {
        ep: SpanArray.from_spans(out_span_partitions[ep]).sorted_by_start()
        for ep in out_eps
    }


def perfect_cut_windows(in_spans: List[Span], max_size: int) -> List[Tuple[int, int]]:
    """Segment sorted incoming spans at points where every earlier span has
    ended (candidate sets provably disjoint), capping segment length.

    Returns [start, end) index pairs.
    """
    n = len(in_spans)
    windows = []
    seg_start = 0
    running_max_end = -math.inf
    for i in range(n):
        s = float(in_spans[i].start_mus)
        if i > seg_start and running_max_end <= s:
            windows.append((seg_start, i))
            seg_start = i
        elif i - seg_start >= max_size:
            windows.append((seg_start, i))
            seg_start = i
        running_max_end = max(running_max_end, float(in_spans[i].start_mus)
                              + float(in_spans[i].duration_mus))
    if seg_start < n:
        windows.append((seg_start, n))
    return windows


def perfect_cut_windows_cols(cols: SpanArray,
                             max_size: int) -> List[Tuple[int, int]]:
    """Columnar :func:`perfect_cut_windows`: the running-max-of-ends cut
    condition never resets across cuts, so the perfect cut points are a
    pure function of the global end-time cummax — one vectorized pass —
    and the ``max_size`` cap then splits each perfect segment into
    fixed-stride chunks (exactly the positions the sequential loop's
    ``i - seg_start >= max_size`` check fires at). Same [start, end)
    pairs as the object version on the same sorted spans (parity-tested).
    """
    n = len(cols)
    if n == 0:
        return []
    cut = np.zeros(n, dtype=bool)
    if n > 1:
        cut[1:] = np.maximum.accumulate(cols.end)[:-1] <= cols.start[1:]
    bounds = [0, *np.flatnonzero(cut).tolist(), n]
    windows: List[Tuple[int, int]] = []
    for a, b in zip(bounds[:-1], bounds[1:]):
        if a == b:
            continue
        for s in range(a, b, max_size):
            windows.append((s, min(s + max_size, b)))
    return windows


def window_index(windows) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """``(w_of, i_of, pos)`` of a packed window batch: for every incoming
    span, in window order, its window row, its row inside that window,
    and its position in the item's sorted incoming spans.

    A few whole-array passes over the ``[lo, hi)`` pairs, however many
    windows there are: hotel windows hold one or two spans each, so a
    per-window ``np.arange`` costs more than the rest of decode. Every
    per-span reduction of a batch reads this one index.
    """
    bounds = np.fromiter(chain.from_iterable(windows), np.int64,
                         2 * len(windows)).reshape(-1, 2)
    lens = bounds[:, 1] - bounds[:, 0]
    w_of = np.repeat(np.arange(bounds.shape[0]), lens)
    first = np.cumsum(lens) - lens
    i_of = np.arange(w_of.size) - first[w_of]
    pos = bounds[w_of, 0] + i_of
    return w_of, i_of, pos


def scatter_window_span_stats(index, not_best, feas,
                              span_not_best, span_cands) -> None:
    """Per-span confidence reductions over a packed window batch, written
    into the caller's ``[n_in]`` arrays in place: a span is "not best"
    when any endpoint's OT choice overrode the row argmax, and its
    candidate count is the product of per-endpoint feasible counts.

    Vectorized over the batch's :func:`window_index` (one fancy-gather
    per batch instead of a Python loop per span) — decode sits on the
    dispatch pipeline's critical path, so per-span Python work here
    would gate the whole fleet solve.
    """
    w_of, i_of, pos = index
    span_not_best[pos] = not_best[w_of, :, i_of].any(axis=1)
    # int64 accumulator matches np.prod's platform-int promotion in the
    # scalar form this replaces
    span_cands[pos] = np.maximum(
        feas[w_of, :, i_of], 1).astype(np.int64).prod(axis=1)


def _bucket(n: int, minimum: int = 8) -> int:
    """Round up to a power of two (bounds jit recompilation variants).
    Wraps the shared :func:`traceweaver_tpu.runtime.bucketing.pow2_bucket`
    with the sublane-tile minimum the dispatch shapes want."""
    return pow2_bucket(n, minimum)


def _window_bounds(windows: List[Tuple[int, int]], start: np.ndarray,
                   end: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Per-window [first in start, max in end] bounds from columns: the
    end-time segment maxes ride ONE ``np.maximum.reduceat`` over the
    interleaved (lo, hi) boundary list instead of a Python max() per
    window."""
    B = len(windows)
    los = np.fromiter((lo for lo, _ in windows), np.int64, B)
    his = np.fromiter((hi for _, hi in windows), np.int64, B)
    idx = np.empty(2 * B, dtype=np.int64)
    idx[0::2] = los
    idx[1::2] = his
    n = start.shape[0]
    if idx[-1] >= n:  # reduceat indices must be < n; the last segment
        seg = np.maximum.reduceat(end, idx[:-1])  # runs to the end anyway
    else:
        seg = np.maximum.reduceat(end, idx)
    return start[los], seg[0::2]


def candidate_ranges(
    in_spans: List[Span],
    windows: List[Tuple[int, int]],
    out_eps: List[str],
    out_starts_np: Dict[str, np.ndarray],
    in_cols: Optional[SpanArray] = None,
) -> np.ndarray:
    """[B, E, 2] candidate index ranges: per window and endpoint, the slice
    of that endpoint's time-sorted out-spans starting within the window's
    [first in start, last in end] bound (the tensor analogue of the
    reference's per-endpoint binary-search cutoffs, traceweaver_v3.py:182-217).
    Single source of truth for both packing and the dispatch-size budget.

    Columnar (``TW_COLUMNAR``, default; or ``in_cols`` given): the window
    bounds come from the start/end columns and each endpoint's two
    cutoffs are ONE vectorized ``searchsorted`` over all windows — no
    per-window Python. The object loop below is the ``TW_COLUMNAR=0``
    reference; both produce identical int64 ranges (parity-tested).
    """
    if in_cols is not None or columnar_enabled():
        if in_cols is None:
            in_cols = in_columns(in_spans)
        if not windows:
            return np.zeros((0, len(out_eps), 2), dtype=np.int64)
        w_t0, w_t1 = _window_bounds(windows, in_cols.start, in_cols.end)
        ranges = np.zeros((len(windows), len(out_eps), 2), dtype=np.int64)
        for e, ep in enumerate(out_eps):
            starts = out_starts_np[ep]
            ranges[:, e, 0] = np.searchsorted(starts, w_t0, side="left")
            ranges[:, e, 1] = np.searchsorted(starts, w_t1, side="right")
        return ranges
    ranges = np.zeros((len(windows), len(out_eps), 2), dtype=np.int64)
    for b, (lo, hi) in enumerate(windows):
        w_t0 = float(in_spans[lo].start_mus)
        w_t1 = max(float(s.start_mus) + float(s.duration_mus)
                   for s in in_spans[lo:hi])
        for e, ep in enumerate(out_eps):
            starts = out_starts_np[ep]
            ranges[b, e, 0] = np.searchsorted(starts, w_t0, side="left")
            ranges[b, e, 1] = np.searchsorted(starts, w_t1, side="right")
    return ranges


class EndpointIds:
    """Decode-time id map for one endpoint of a packed batch: instead of
    materializing a ``[None] * (B * M)`` Python list at pack time (the
    object path's layout — B·M object slots, mostly None), the columnar
    path keeps the endpoint's sorted id TABLE plus each window row's
    ``(r0, count)`` candidate range and gathers ids only when the decode
    actually needs them — one fancy-index gather per endpoint per batch.
    """

    __slots__ = ("table", "r0", "count", "M")

    def __init__(self, table: np.ndarray, r0: np.ndarray, count: np.ndarray,
                 M: int) -> None:
        self.table = table      # [n_ep_spans] object — ascending-start ids
        self.r0 = r0            # [B] int64 — first candidate per window row
        self.count = count      # [B] int64 — candidates per window row
        self.M = M              # padded column count

    def rows(self, n: int) -> "EndpointIds":
        """First ``n`` window rows (the fleet packer's row truncation)."""
        return EndpointIds(self.table, self.r0[:n], self.count[:n], self.M)

    def gather(self) -> np.ndarray:
        """Materialize the object path's ``[B * M]`` id layout (None in
        empty slots) — same indexing contract (``b * M + j``), produced
        by one table gather instead of per-span list writes."""
        B, M = self.r0.shape[0], self.M
        j = np.arange(M)
        valid = j[None, :] < self.count[:, None]
        src = np.where(valid, self.r0[:, None] + j[None, :], 0)
        out = np.full((B, M), None, dtype=object)
        out[valid] = self.table[src[valid]]
        return out.reshape(B * M)


@dataclass
class PackedProblem:
    """Dense window tensors + the index maps to decode device output.

    ``out_ids`` holds, per endpoint, either the object path's flat
    ``[B * M]`` id list or the columnar path's :class:`EndpointIds`
    (id-table + ranges, gathered at decode time): decode reads either
    through :func:`_candidate_keys`, and :meth:`out_id_array` gives
    both in the object path's layout.

    ``devcols`` (``TW_DEVCOLS`` fleet path only) replaces the six big
    window tensors with ring-slot INDEX arrays plus the owning
    :class:`~traceweaver_tpu.ops.devcols.ColumnRing` handles — the
    window tensors themselves are assembled on device from the resident
    columns (:func:`traceweaver_tpu.ops.devcols.assemble_windows`) and
    never exist in host memory. ``arrays`` then carries only the small
    host-shipped tensors (skip_cap/force_skip) and the problem tables.
    """

    arrays: Dict[str, np.ndarray]
    out_eps: List[str]
    windows: List[Tuple[int, int]]
    in_ids: List  # [n_in] span ids, window order == original sort order
    out_ids: List  # per ep: [B*M] id list OR EndpointIds
    n_in: int
    devcols: Optional[Dict] = None

    @property
    def M(self) -> int:
        """Padded candidate-column count (the decode stride)."""
        if self.devcols is not None:
            return int(self.devcols["out_idx"].shape[2])
        return int(self.arrays["out_start"].shape[2])

    def out_id_array(self, e: int) -> np.ndarray:
        """[B * M] object array of candidate ids for endpoint ``e``."""
        col = self.out_ids[e]
        if isinstance(col, EndpointIds):
            return col.gather()
        ids = np.empty(len(col), dtype=object)
        ids[:] = col
        return ids

    def truncate_rows(self, n_rows: int) -> None:
        """Drop the power-of-two B padding from the id maps (the fleet
        packer slices every batch tensor to its exact window count; the
        id maps must follow so decode's ``b * M + j`` indexing stays
        aligned)."""
        M = self.M
        self.out_ids = [
            col.rows(n_rows) if isinstance(col, EndpointIds)
            else col[:n_rows * M]
            for col in self.out_ids
        ]


#: decode's integer codes for the two sentinels; a real candidate's code
#: is its id-table key (>= 0)
NA_KEY = -1
SKIP_KEY = -2


def _candidate_keys(col, M: int, w: np.ndarray, cols: np.ndarray):
    """Integer keys of candidate columns ``cols`` (0..M-1) of window rows
    ``w`` for one endpoint, :data:`NA_KEY` where the slot holds no id,
    and the id table the keys index.

    On the columnar path (:class:`EndpointIds`) a key is the candidate's
    row in the endpoint's id table, so equal keys are the same out-span
    across every window of the batch. On the object path it is the
    batch slot ``w * M + col``: unique within a window row only.
    """
    if isinstance(col, EndpointIds):
        return (np.where(cols < col.count[w], col.r0[w] + cols, NA_KEY),
                col.table)
    ids = np.empty(len(col), dtype=object)
    ids[:] = col
    slot = w * M + cols
    return np.where(ids[slot] != None, slot, NA_KEY), ids  # noqa: E711


def _claims_in_order(assign_map, in_ids) -> Tuple[Dict, int]:
    """Every claimed out-span with the positions (into ``in_ids``) of the
    incoming spans that claimed it, in order of first claim, and the
    number of SKIP answers."""
    claims: Dict = {}
    skips_used = 0
    for p, in_id in enumerate(in_ids):
        out_id = assign_map.get(in_id)
        if out_id == SKIP:
            skips_used += 1
        elif out_id is not None and out_id != NA:
            claims.setdefault(out_id, []).append(p)
    return claims, skips_used


def _reassign_losers(assign_map, topk_map, in_ids, claims, used: set,
                     skips_used: int, budget: int) -> List:
    """The serial rule of
    :meth:`WeaverTPU._resolve_cross_window_duplicates` over ``claims``
    (position lists, first claimant first, in order of first claim):
    every later claimant takes its best-ranked top-K alternative that
    ``used`` does not hold, SKIP only while ``budget`` has room, else NA.
    Returns the positions of the claimants it reassigned."""
    losers = []
    for claimants in claims:
        for p in claimants[1:]:  # earliest claimant keeps it
            in_id = in_ids[p]
            replacement = NA
            for cand in topk_map.get(in_id, []):
                if cand == SKIP:
                    if skips_used < budget:
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
            tk = topk_map.get(in_id)
            if tk and replacement in tk:
                tk.remove(replacement)
                tk.insert(0, replacement)
            losers.append(p)
    return losers


def _problem_tables(out_eps: List[str], E_pad: int,
                    dists: Dict[Tuple[str, str], EdgeDist], in_ep: str,
                    dag: Optional[nx.DiGraph],
                    parallel: bool) -> Dict[str, np.ndarray]:
    """DAG structure masks + distribution param tables of one problem —
    identical for the columnar and object pack paths (one definition, so
    the golden parity holds by construction on everything that is not a
    window tensor)."""
    E = len(out_eps)
    pred_mask = np.zeros((E_pad, E_pad), dtype=bool)
    root_mask = np.zeros((E_pad,), dtype=bool)
    is_last = np.zeros((E_pad,), dtype=bool)
    if parallel or dag is None:
        root_mask[:E] = True
    else:
        for e, ep in enumerate(out_eps):
            preds = timing.primary_pred_edges(dag, ep)
            if len(dag.in_edges(ep)) == 0 or in_ep in preds:
                root_mask[e] = True
            for p in preds:
                if p != in_ep and p in out_eps:
                    pred_mask[e, out_eps.index(p)] = True
        is_last[E - 1] = True

    K = MAX_COMPONENTS
    wide = EdgeDist.gaussian(0.0, 1e7)  # near-flat fallback for unseen edges

    def params_of(key) -> EdgeDist:
        return dists.get(key, wide)

    edge_wt = np.zeros((E_pad, E_pad, K), dtype=np.float32)
    edge_mu = np.zeros((E_pad, E_pad, K), dtype=np.float32)
    edge_sd = np.ones((E_pad, E_pad, K), dtype=np.float32)
    in_wt = np.zeros((E_pad, K), dtype=np.float32)
    in_mu = np.zeros((E_pad, K), dtype=np.float32)
    in_sd = np.ones((E_pad, K), dtype=np.float32)
    ret_wt = np.zeros((E_pad, K), dtype=np.float32)
    ret_mu = np.zeros((E_pad, K), dtype=np.float32)
    ret_sd = np.ones((E_pad, K), dtype=np.float32)
    for e, ep in enumerate(out_eps):
        d = params_of((in_ep, ep))
        in_wt[e], in_mu[e], in_sd[e] = d.weights, d.means, d.stds
        d = params_of((ep, in_ep))
        ret_wt[e], ret_mu[e], ret_sd[e] = d.weights, d.means, d.stds
        for p, pep in enumerate(out_eps):
            d = params_of((pep, ep))
            edge_wt[e, p], edge_mu[e, p], edge_sd[e, p] = d.weights, d.means, d.stds

    return dict(
        pred_mask=pred_mask, root_mask=root_mask, is_last=is_last,
        edge_wt=edge_wt, edge_mu=edge_mu, edge_sd=edge_sd,
        in_wt=in_wt, in_mu=in_mu, in_sd=in_sd,
        ret_wt=ret_wt, ret_mu=ret_mu, ret_sd=ret_sd,
    )


def dists_from_tables(out_eps: List[str], in_ep: str,
                      edge_wt, edge_mu, edge_sd,
                      in_wt, in_mu, in_sd,
                      ret_wt, ret_mu, ret_sd
                      ) -> Dict[Tuple[str, str], EdgeDist]:
    """Inverse of the ``_problem_tables`` packing: fitted param tables
    (one service's rows, refit order — the nine-tuple
    :func:`refit_fleet_params` returns) back into the solver's
    ``{(parent_ep, child_ep): EdgeDist}`` dict.

    Decodes EVERY family row over the true edges ``e < len(out_eps)``,
    including edges the refit saw no samples for — the in-graph fit
    keeps the prior params for empty rows (ops/gmm.fit_gmm_in_graph), so
    repacking the decoded dict through ``_problem_tables`` reproduces
    the device tables bit-exactly (f32 -> f64 -> f32 round-trips
    losslessly). That exactness is what lets the plan cache admit
    on-device refit results and stay byte-identical on the next solve."""
    def mk(w, m, s) -> EdgeDist:
        return EdgeDist(np.asarray(w, dtype=np.float64),
                        np.asarray(m, dtype=np.float64),
                        np.asarray(s, dtype=np.float64))

    dists: Dict[Tuple[str, str], EdgeDist] = {}
    for e, ep in enumerate(out_eps):
        dists[(in_ep, ep)] = mk(in_wt[e], in_mu[e], in_sd[e])
        dists[(ep, in_ep)] = mk(ret_wt[e], ret_mu[e], ret_sd[e])
        for p, pep in enumerate(out_eps):
            dists[(pep, ep)] = mk(edge_wt[e, p], edge_mu[e, p],
                                  edge_sd[e, p])
    return dists


def pack_problem(
    in_spans: List[Span],
    out_span_partitions: Dict[str, List[Span]],
    out_eps: List[str],
    dists: Dict[Tuple[str, str], EdgeDist],
    in_ep: str,
    dag: Optional[nx.DiGraph],
    force_skip_ids: Optional[Dict[str, set]] = None,
    max_window: int = DEFAULT_MAX_WINDOW,
    parallel: bool = False,
    windows: Optional[List[Tuple[int, int]]] = None,
    pad_w: Optional[int] = None,
    pad_b: Optional[int] = None,
    pad_m: Optional[int] = None,
    pad_e: Optional[int] = None,
    ranges: Optional[np.ndarray] = None,
    skip_caps: Optional[np.ndarray] = None,  # [len(windows), E] water-filled
    in_cols: Optional[SpanArray] = None,
    out_cols: Optional[Dict[str, SpanArray]] = None,
) -> PackedProblem:
    """Build the dense [B, ...] window tensors for :func:`solve_windows`.

    ``windows`` (index pairs into the sorted ``in_spans``) may be supplied to
    pack a subset; when omitted, perfect cuts over the whole stream are used.
    ``pad_w``/``pad_b``/``pad_m`` force the padded window width / batch size /
    candidate-column count (all still rounded up to powers of two) so every
    chunk of a solve shares one compiled variant. ``pad_e`` pads the endpoint
    axis (fleet packing: services share one dispatch at the fleet-max E;
    padded endpoints carry no valid columns, a false root/pred/last mask and
    unit-σ zero-weight params, so the solve ignores them).

    Two implementations behind one contract (byte-identical tensors,
    identical decode — the golden parity suite pins it):

    - **columnar** (``TW_COLUMNAR=1``, the default): window rows are
      strided slices of the partition's :class:`SpanArray` columns
      (``in_cols``/``out_cols``, converted here when the caller did not
      hand them over), candidate blocks are fancy-index gathers, and the
      id maps stay :class:`EndpointIds` tables resolved at decode time —
      no per-span Python anywhere in the fill;
    - **object** (``TW_COLUMNAR=0``): the original per-window span-object
      walk, kept verbatim as the kill switch and parity reference.
    """
    if columnar_enabled():
        return _pack_problem_columnar(
            in_spans, out_span_partitions, out_eps, dists, in_ep, dag,
            force_skip_ids=force_skip_ids, max_window=max_window,
            parallel=parallel, windows=windows, pad_w=pad_w, pad_b=pad_b,
            pad_m=pad_m, pad_e=pad_e, ranges=ranges, skip_caps=skip_caps,
            in_cols=in_cols, out_cols=out_cols)
    return _pack_problem_objects(
        in_spans, out_span_partitions, out_eps, dists, in_ep, dag,
        force_skip_ids=force_skip_ids, max_window=max_window,
        parallel=parallel, windows=windows, pad_w=pad_w, pad_b=pad_b,
        pad_m=pad_m, pad_e=pad_e, ranges=ranges, skip_caps=skip_caps)


def _pack_problem_columnar(
    in_spans, out_span_partitions, out_eps, dists, in_ep, dag,
    force_skip_ids=None, max_window=DEFAULT_MAX_WINDOW, parallel=False,
    windows=None, pad_w=None, pad_b=None, pad_m=None, pad_e=None,
    ranges=None, skip_caps=None, in_cols=None, out_cols=None,
) -> PackedProblem:
    """Columnar :func:`pack_problem` body: every window tensor is filled
    by array slicing/gather over the partition columns. The per-span
    Python of the object path — ``[float(s.start_mus) for s in ...]`` per
    window per endpoint, an id-list write per candidate slot — becomes
    O(1) NumPy statements per endpoint, so pack cost scales with array
    size, not span-object count (the 0.39% MFU host stall of
    PROFILE_r05, docs/PERF.md "Columnar host path")."""
    E = len(out_eps)
    E_pad = max(E, pad_e or E)
    if in_cols is None:
        in_cols = in_columns(in_spans)
    if out_cols is None:
        out_cols = out_columns(out_span_partitions, out_eps)
    if windows is None:
        windows = perfect_cut_windows_cols(in_cols, max_window)
    n_windows = len(windows)
    B = _bucket(max(n_windows, pad_b or 1), minimum=1)
    W = _bucket(max(max(hi - lo for lo, hi in windows), pad_w or 1))

    if ranges is None:  # caller may pass precomputed rows (same helper)
        out_starts_np = {ep: out_cols[ep].start for ep in out_eps}
        ranges = candidate_ranges(in_spans, windows, out_eps, out_starts_np,
                                  in_cols=in_cols)
    M = _bucket(max(int((ranges[:, :, 1] - ranges[:, :, 0]).max(initial=1)),
                    pad_m or 1))

    in_start = np.zeros((B, W), dtype=np.float32)
    in_end = np.zeros((B, W), dtype=np.float32)
    in_valid = np.zeros((B, W), dtype=bool)
    out_start = np.zeros((B, E_pad, M), dtype=np.float32)
    out_end = np.zeros((B, E_pad, M), dtype=np.float32)
    out_valid = np.zeros((B, E_pad, M), dtype=bool)
    skip_cap = np.zeros((B, E_pad), dtype=np.float32)
    force_skip = np.zeros((B, E_pad, W), dtype=bool)

    los = np.fromiter((lo for lo, _ in windows), np.int64, n_windows)
    his = np.fromiter((hi for _, hi in windows), np.int64, n_windows)
    n_w = his - los
    origins = in_cols.start[los]                       # [Bw] f64

    # incoming rows: one strided gather for the whole batch
    jw = np.arange(W)
    w_valid = jw[None, :] < n_w[:, None]               # [Bw, W]
    w_src = np.where(w_valid, los[:, None] + jw[None, :], 0)
    in_start[:n_windows][w_valid] = (
        in_cols.start[w_src] - origins[:, None])[w_valid]
    in_end[:n_windows][w_valid] = (
        in_cols.end[w_src] - origins[:, None])[w_valid]
    in_valid[:n_windows] = w_valid

    # candidate blocks: one gather per endpoint
    jm = np.arange(M)
    r0 = ranges[:, :, 0]                               # [Bw, E]
    m_w = ranges[:, :, 1] - r0                         # [Bw, E]
    out_ids: List[EndpointIds] = []
    for e, ep in enumerate(out_eps):
        cols = out_cols[ep]
        c_valid = jm[None, :] < m_w[:, e][:, None]     # [Bw, M]
        c_src = np.where(c_valid, r0[:, e][:, None] + jm[None, :], 0)
        out_start[:n_windows, e][c_valid] = (
            cols.start[c_src] - origins[:, None])[c_valid]
        out_end[:n_windows, e][c_valid] = (
            cols.end[c_src] - origins[:, None])[c_valid]
        out_valid[:n_windows, e] = c_valid
        # id map resolved at decode time: table + per-row ranges, padded
        # to the bucketed B so gather() reproduces the [B*M] layout
        r0_pad = np.zeros(B, dtype=np.int64)
        cnt_pad = np.zeros(B, dtype=np.int64)
        r0_pad[:n_windows] = r0[:, e]
        cnt_pad[:n_windows] = m_w[:, e]
        out_ids.append(EndpointIds(cols.ids, r0_pad, cnt_pad, M))

    # skip capacity: water-filled budget when provided (reference
    # TallySkipSpans semantics); the solver still grants window-local
    # slack max(rows - cols, 0) on device for feasibility
    if skip_caps is not None:
        skip_cap[:n_windows, :E] = skip_caps
    else:
        skip_cap[:n_windows, :E] = np.maximum(n_w[:, None] - m_w, 0)

    if force_skip_ids:
        in_ids_arr = in_cols.ids
        for e, ep in enumerate(out_eps):
            fs = force_skip_ids.get(ep, set())
            if not fs:
                continue
            for b in range(n_windows):
                lo, hi = int(los[b]), int(his[b])
                mask = np.fromiter((i in fs for i in in_ids_arr[lo:hi]),
                                   bool, hi - lo)
                n_forced = int(mask.sum())
                if n_forced:
                    force_skip[b, e, :hi - lo] = mask
                # every forced row needs skip capacity even when candidate
                # ranges inflated by neighbouring windows hide the slack
                skip_cap[b, e] = max(skip_cap[b, e], n_forced)

    arrays = dict(
        in_start=in_start, in_end=in_end, in_valid=in_valid,
        out_start=out_start, out_end=out_end, out_valid=out_valid,
        skip_cap=skip_cap, force_skip=force_skip,
        **_problem_tables(out_eps, E_pad, dists, in_ep, dag, parallel),
    )
    return PackedProblem(arrays=arrays, out_eps=out_eps, windows=windows,
                         in_ids=in_cols.ids, out_ids=out_ids,
                         n_in=len(in_cols))


def _pack_problem_devcols(
    in_spans, out_span_partitions, out_eps, dists, in_ep, dag,
    in_slots, out_slots, ring_in, ring_out,
    force_skip_ids=None, max_window=DEFAULT_MAX_WINDOW, parallel=False,
    windows=None, pad_w=None, pad_b=None, pad_m=None, pad_e=None,
    ranges=None, skip_caps=None, in_cols=None, out_cols=None,
) -> PackedProblem:
    """Device-resident :func:`pack_problem` body (``TW_DEVCOLS``, fleet
    path): the SAME windowing, candidate ranges, skip caps, id maps, and
    problem tables as :func:`_pack_problem_columnar`, but instead of
    filling the six dense window tensors in host memory it emits int32
    ring-slot INDEX arrays (``in_idx [B, W]`` / ``out_idx [B, E, M]``,
    −1 = invalid) over the resident device columns
    (:mod:`traceweaver_tpu.ops.devcols`). The tensors themselves are
    assembled by on-device gathers at dispatch time — bit-identical to
    the host fill on the integral-µs timestamps the ring admits (the
    ``TW_DEVCOLS`` parity suite pins it).

    ``in_slots`` / ``out_slots[ep]`` map each sorted partition position
    to its live ring slot (``ColumnRing.resolve``); the caller resolved
    them before packing, so ineligible partitions never reach here."""
    E = len(out_eps)
    E_pad = max(E, pad_e or E)
    if in_cols is None:
        in_cols = in_columns(in_spans)
    if out_cols is None:
        out_cols = out_columns(out_span_partitions, out_eps)
    if windows is None:
        windows = perfect_cut_windows_cols(in_cols, max_window)
    n_windows = len(windows)
    B = _bucket(max(n_windows, pad_b or 1), minimum=1)
    W = _bucket(max(max(hi - lo for lo, hi in windows), pad_w or 1))

    if ranges is None:
        out_starts_np = {ep: out_cols[ep].start for ep in out_eps}
        ranges = candidate_ranges(in_spans, windows, out_eps, out_starts_np,
                                  in_cols=in_cols)
    M = _bucket(max(int((ranges[:, :, 1] - ranges[:, :, 0]).max(initial=1)),
                    pad_m or 1))

    skip_cap = np.zeros((B, E_pad), dtype=np.float32)
    force_skip = np.zeros((B, E_pad, W), dtype=bool)
    in_idx = np.full((B, W), -1, dtype=np.int32)
    out_idx = np.full((B, E_pad, M), -1, dtype=np.int32)
    origin_in = np.zeros(B, dtype=np.int32)
    origin_out = np.zeros(B, dtype=np.int32)

    los = np.fromiter((lo for lo, _ in windows), np.int64, n_windows)
    his = np.fromiter((hi for _, hi in windows), np.int64, n_windows)
    n_w = his - los
    origins = in_cols.start[los]                       # [Bw] f64 absolute
    origin_in[:n_windows] = ring_in.rel32(origins)
    origin_out[:n_windows] = ring_out.rel32(origins)

    jw = np.arange(W)
    w_valid = jw[None, :] < n_w[:, None]               # [Bw, W]
    w_src = np.where(w_valid, los[:, None] + jw[None, :], 0)
    in_idx[:n_windows][w_valid] = in_slots[w_src][w_valid]

    jm = np.arange(M)
    r0 = ranges[:, :, 0]
    m_w = ranges[:, :, 1] - r0
    out_ids: List[EndpointIds] = []
    for e, ep in enumerate(out_eps):
        cols = out_cols[ep]
        c_valid = jm[None, :] < m_w[:, e][:, None]     # [Bw, M]
        c_src = np.where(c_valid, r0[:, e][:, None] + jm[None, :], 0)
        out_idx[:n_windows, e][c_valid] = out_slots[ep][c_src][c_valid]
        r0_pad = np.zeros(B, dtype=np.int64)
        cnt_pad = np.zeros(B, dtype=np.int64)
        r0_pad[:n_windows] = r0[:, e]
        cnt_pad[:n_windows] = m_w[:, e]
        out_ids.append(EndpointIds(cols.ids, r0_pad, cnt_pad, M))

    if skip_caps is not None:
        skip_cap[:n_windows, :E] = skip_caps
    else:
        skip_cap[:n_windows, :E] = np.maximum(n_w[:, None] - m_w, 0)

    if force_skip_ids:
        in_ids_arr = in_cols.ids
        for e, ep in enumerate(out_eps):
            fs = force_skip_ids.get(ep, set())
            if not fs:
                continue
            for b in range(n_windows):
                lo, hi = int(los[b]), int(his[b])
                mask = np.fromiter((i in fs for i in in_ids_arr[lo:hi]),
                                   bool, hi - lo)
                n_forced = int(mask.sum())
                if n_forced:
                    force_skip[b, e, :hi - lo] = mask
                skip_cap[b, e] = max(skip_cap[b, e], n_forced)

    arrays = dict(
        skip_cap=skip_cap, force_skip=force_skip,
        **_problem_tables(out_eps, E_pad, dists, in_ep, dag, parallel),
    )
    return PackedProblem(
        arrays=arrays, out_eps=out_eps, windows=windows,
        in_ids=in_cols.ids, out_ids=out_ids, n_in=len(in_cols),
        devcols=dict(in_idx=in_idx, out_idx=out_idx,
                     origin_in=origin_in, origin_out=origin_out,
                     ring_in=ring_in, ring_out=ring_out))


def _pack_problem_objects(
    in_spans, out_span_partitions, out_eps, dists, in_ep, dag,
    force_skip_ids=None, max_window=DEFAULT_MAX_WINDOW, parallel=False,
    windows=None, pad_w=None, pad_b=None, pad_m=None, pad_e=None,
    ranges=None, skip_caps=None,
) -> PackedProblem:
    """Object-walk :func:`pack_problem` body (``TW_COLUMNAR=0``): the
    pre-columnar per-window span loops, kept verbatim as the kill switch
    and the golden-parity reference."""
    E = len(out_eps)
    E_pad = max(E, pad_e or E)
    if windows is None:
        windows = perfect_cut_windows(in_spans, max_window)
    n_windows = len(windows)
    B = _bucket(max(n_windows, pad_b or 1), minimum=1)
    W = _bucket(max(max(hi - lo for lo, hi in windows), pad_w or 1))

    out_sorted = {
        ep: sorted(out_span_partitions[ep], key=lambda s: s.start_mus)
        for ep in out_eps
    }
    out_starts_np = {
        ep: np.array([float(s.start_mus) for s in out_sorted[ep]]) for ep in out_eps
    }

    if ranges is None:  # caller may pass precomputed rows (same helper)
        ranges = candidate_ranges(in_spans, windows, out_eps, out_starts_np)
    M = _bucket(max(int((ranges[:, :, 1] - ranges[:, :, 0]).max(initial=1)),
                    pad_m or 1))

    in_start = np.zeros((B, W), dtype=np.float32)
    in_end = np.zeros((B, W), dtype=np.float32)
    in_valid = np.zeros((B, W), dtype=bool)
    out_start = np.zeros((B, E_pad, M), dtype=np.float32)
    out_end = np.zeros((B, E_pad, M), dtype=np.float32)
    out_valid = np.zeros((B, E_pad, M), dtype=bool)
    skip_cap = np.zeros((B, E_pad), dtype=np.float32)
    force_skip = np.zeros((B, E_pad, W), dtype=bool)

    out_ids: List[List] = [[None] * (B * M) for _ in range(E)]
    in_ids = [s.GetId() for s in in_spans]

    for b, (lo, hi) in enumerate(windows):
        origin = float(in_spans[lo].start_mus)
        n_w = hi - lo
        in_start[b, :n_w] = [float(s.start_mus) - origin for s in in_spans[lo:hi]]
        in_end[b, :n_w] = [
            float(s.start_mus) + float(s.duration_mus) - origin
            for s in in_spans[lo:hi]
        ]
        in_valid[b, :n_w] = True
        for e, ep in enumerate(out_eps):
            r0, r1 = int(ranges[b, e, 0]), int(ranges[b, e, 1])
            m_w = r1 - r0
            cands = out_sorted[ep][r0:r1]
            out_start[b, e, :m_w] = [float(s.start_mus) - origin for s in cands]
            out_end[b, e, :m_w] = [
                float(s.start_mus) + float(s.duration_mus) - origin for s in cands
            ]
            out_valid[b, e, :m_w] = True
            for j, s in enumerate(cands):
                out_ids[e][b * M + j] = s.GetId()
            # water-filled budget when provided (reference TallySkipSpans
            # semantics); the solver still grants window-local slack
            # max(rows - cols, 0) on device for feasibility
            skip_cap[b, e] = (float(skip_caps[b, e]) if skip_caps is not None
                              else max(0, n_w - m_w))
            if force_skip_ids:
                fs = force_skip_ids.get(ep, set())
                n_forced = 0
                for i, s in enumerate(in_spans[lo:hi]):
                    if s.GetId() in fs:
                        force_skip[b, e, i] = True
                        n_forced += 1
                # every forced row needs skip capacity even when candidate
                # ranges inflated by neighbouring windows hide the slack
                skip_cap[b, e] = max(skip_cap[b, e], n_forced)

    arrays = dict(
        in_start=in_start, in_end=in_end, in_valid=in_valid,
        out_start=out_start, out_end=out_end, out_valid=out_valid,
        skip_cap=skip_cap, force_skip=force_skip,
        **_problem_tables(out_eps, E_pad, dists, in_ep, dag, parallel),
    )
    return PackedProblem(arrays=arrays, out_eps=out_eps, windows=windows,
                         in_ids=in_ids, out_ids=out_ids, n_in=len(in_spans))


def plan_find_assignments(
    in_span_partitions: Dict[str, List[Span]],
    out_span_partitions: Dict[str, List[Span]],
    out_eps: List[str],
    dag,
    true_assignments,
    score_mode: str = "mixture",
    true_skips: bool = False,
    true_dist: bool = False,
    parallel_mode: bool = False,
    skip_fit: bool = False,
) -> Dict:
    """The solve plan shared by the per-service entry point
    (:meth:`WeaverTPU.FindAssignments`) and the fleet packer
    (:func:`traceweaver_tpu.algorithms.fleet._prepare`): per-endpoint
    skip budgets (reference traceweaver_v3.py:972), the dynamism flag,
    forced-skip rows for the true-skips oracle, initial distributions
    (bootstrap under dynamism / missing DAG, graph-aware batch means
    otherwise, oracle truth under true_dist) and the iteration count.
    ONE definition so the two production paths cannot drift.

    ``skip_fit=True`` skips ONLY the distribution fit (``dists`` comes
    back empty) — for callers that will override dists anyway (a warm
    carried state or a plan-cache hit), where the host BIC sweeps are
    the round's dominant serial stage and pure dead computation.
    Budgets, dynamism, forced skips and the iteration count are computed
    identically, so the plan is otherwise byte-for-byte the same.
    """
    in_ep = next(iter(in_span_partitions))
    n_in = len(in_span_partitions[in_ep])
    skip_budget = {
        ep: n_in - len(out_span_partitions[ep]) for ep in out_eps
    }
    dynamism = any(b > 0 for b in skip_budget.values())

    force_skip_ids = None
    if true_skips:
        force_skip_ids = {
            ep: {
                in_id for in_id, out_id in true_assignments[ep].items()
                if tuple(out_id) == SKIP
            }
            for ep in out_eps
        }

    if skip_fit:
        dists = {}
    elif true_dist:
        dists = timing.true_distributions(
            in_span_partitions, out_span_partitions, out_eps,
            true_assignments, score_mode=score_mode,
        )
    elif dynamism or dag is None:
        dists = timing.bootstrap_distributions(
            in_span_partitions, out_span_partitions, out_eps,
            score_mode=score_mode,
        )
    else:
        dists = timing.estimate_edge_params(
            in_span_partitions, out_span_partitions, dag, 0, n_in,
        )

    iterations = 1 if (parallel_mode or dynamism or true_dist) else 2
    return dict(skip_budget=skip_budget, dynamism=dynamism,
                force_skip_ids=force_skip_ids, dists=dists,
                iterations=iterations, n_in=n_in, in_ep=in_ep)


# ---------------------------------------------------------------------------
# The plugin-facing solver class
# ---------------------------------------------------------------------------

class WeaverTPU:
    """TraceWeaverV3-capability solver behind the plugin contract.

    Registered at predictor indices 8/9/10
    (``MaxScoreBatchParallelWithoutIterations`` / ``MaxScoreBatchParallel``
    / ``MaxScoreBatchSubsetWithSkips``); also accepts the oracle ablation
    methods ``MaxScoreBatchSubsetWithTrueSkips`` / ``WithTrueDist``
    (reference executor.py:976-987).
    """

    def __init__(self, all_spans, all_processes, max_window: int = DEFAULT_MAX_WINDOW,
                 epsilon: float = 1.0, n_sinkhorn: int = 40, n_sweeps: int = 5,
                 mesh=None, score_mode: str = "mixture",
                 sinkhorn_tol: float = 1e-3, precision: Optional[str] = None):
        self.all_spans = all_spans
        self.all_processes = all_processes
        self.max_window = max_window
        self.epsilon = epsilon
        self.n_sinkhorn = n_sinkhorn
        self.n_sweeps = n_sweeps
        # score-block storage precision ("f32" default — bit-identical
        # historical program — or "bf16"; see ops/precision.py). None
        # reads TW_PRECISION at construction time.
        self.precision = validate_precision(
            precision if precision is not None else precision_from_env())
        # early-exit tolerance for the Sinkhorn potentials (n_sinkhorn stays
        # the hard cap); the Gauss-Seidel sweep loop exits exactly on
        # assignment stability regardless of this value
        self.sinkhorn_tol = sinkhorn_tol
        # optional jax.sharding.Mesh: window batches shard over its first
        # axis (XLA SPMD over ICI); None = single device
        self.mesh = mesh
        # "mixture" (default: Gaussian / BIC-GMM, reference norm+GMM score
        # branches) or "kde" (binned-KDE mixtures, reference
        # traceweaver_v1.py:117-121 KDE branch)
        self.score_mode = score_mode
        # per-solve stage accounting (seconds / analytic op counts),
        # populated by FindAssignments; read by the benchmark
        self.stats: Dict[str, float] = {}
        # per-span reconstruction-quality records of the LAST solve
        # ({in span id: {conf, not_best, cands, support}} —
        # obs/quality.py); the fleet's per-service fallback path copies
        # this into the caller's confidences slot, so fallback windows
        # carry tw.confidence exactly like fused ones
        self.per_span_confidence: Dict = {}

    # -- helpers -----------------------------------------------------------
    @staticmethod
    def _topo_out_eps(out_span_partitions, invocation_graph) -> List[str]:
        if invocation_graph is not None and len(invocation_graph) > 0:
            first_start = {
                ep: spans[0].start_mus if spans else 0
                for ep, spans in out_span_partitions.items()
            }
            return list(
                nx.lexicographical_topological_sort(
                    invocation_graph, key=lambda ep: first_start.get(ep, 0)
                )
            )
        return get_out_eps_in_order(out_span_partitions)

    def _solve_once(self, in_spans, out_span_partitions, out_eps, dists,
                    in_ep, dag, force_skip_ids, parallel, fused=False):
        """Solve all perfect-cut windows in as few device dispatches as
        possible: size classes are merged upward while the padding cost
        stays under MERGE_ELEMS, batches are chunked only to bound live HBM
        (budgeted on the true [B, W, M] block), outputs are packed into a
        single int32 tensor and fetched asynchronously — each device round
        trip has a fixed cost, so dispatch count dominates.

        Returns a list of ``(packed, (assign, topk, not_best, feas))``.
        """
        E = max(1, len(out_eps))
        n_sweeps = 1 if E == 1 else self.n_sweeps

        # columnar host path (TW_COLUMNAR, default): ONE object -> column
        # conversion per partition here; windowing, candidate ranges, and
        # every pack below are array work over these columns
        in_cols = out_cols = None
        if columnar_enabled():
            in_cols = in_columns(in_spans)
            out_cols = out_columns(out_span_partitions, out_eps)
            all_windows = perfect_cut_windows_cols(in_cols, self.max_window)
            out_starts_np = {ep: out_cols[ep].start for ep in out_eps}
        else:
            all_windows = perfect_cut_windows(in_spans, self.max_window)
            out_starts_np = {
                ep: np.array(sorted(float(s.start_mus)
                                    for s in out_span_partitions[ep]))
                for ep in out_eps
            }
        # candidate ranges computed ONCE for all windows (the same rows the
        # packer consumes), so padding costs and the chunk budget reflect
        # the true [B, W, M] block without re-running searchsorted per class
        ranges_all = candidate_ranges(
            in_spans, all_windows, out_eps, out_starts_np, in_cols=in_cols)
        # per-endpoint global skip budget spread across windows by
        # water-filling (reference TallySkipSpans, traceweaver_v3.py:853-989)
        skip_caps_all = water_fill_skip_caps(
            all_windows, ranges_all, len(in_spans),
            [len(out_span_partitions[ep]) for ep in out_eps],
        )
        width_of = {
            w: int((ranges_all[i, :, 1] - ranges_all[i, :, 0]).max(initial=1))
            for i, w in enumerate(all_windows)
        }
        row_of = {w: i for i, w in enumerate(all_windows)}

        def est_m(wins: List[Tuple[int, int]]) -> int:
            return _bucket(max(width_of[w] for w in wins))

        # size classes (power-of-two widths), with smaller classes greedily
        # merged upward while the extra padded area stays under MERGE_ELEMS —
        # one dispatch for typical skews, separate classes when padding a
        # swarm of small windows up to a burst's width would cost more
        # compute than the saved round trip
        groups: Dict[int, List[Tuple[int, int]]] = {}
        for w in all_windows:
            groups.setdefault(_bucket(w[1] - w[0]), []).append(w)
        classes = sorted(groups)
        batches_spec: List[Tuple[int, List[Tuple[int, int]]]] = []
        carry: List[Tuple[int, int]] = []
        for idx, c in enumerate(classes):
            wins = carry + groups[c]
            if idx + 1 < len(classes):
                nxt = classes[idx + 1]
                # charge each window from its ORIGINAL class — a window
                # carried across several merges compounds padding that a
                # per-step (nxt - c) charge would undercount
                extra = sum(nxt - _bucket(hi - lo) for lo, hi in wins)
                if extra * est_m(wins) * E <= MERGE_ELEMS:
                    carry = wins
                    continue
            batches_spec.append((c, wins))
            carry = []

        import time as _time

        # multi-device: window batches shard over the mesh's first axis
        # (XLA SPMD over ICI; see traceweaver_tpu.parallel.mesh) — each
        # device then owns a contiguous slice of windows, so the chunk
        # element budget (per-device HBM) scales by the mesh size
        mesh = self.mesh
        n_dev = 1
        if mesh is not None:
            n_dev = int(mesh.devices.size)
            assert n_dev & (n_dev - 1) == 0, (
                "mesh size must be a power of two so padded window batches "
                "divide evenly across devices")

        stats = self.stats
        # per-dispatch budget in BYTES (CHUNK_ELEMS is denominated in f32
        # elements for knob back-compat): a bf16 score block charges half,
        # so the same HBM bound admits ~2x the windows per dispatch
        itemsize = score_itemsize(self.precision)
        chunk_bytes = CHUNK_ELEMS * 4
        plan = []
        for wclass, wins in batches_spec:
            m_est = est_m(wins)
            per_chunk = max(
                1, chunk_bytes // (wclass * m_est * E * itemsize)) * n_dev
            chunks = [wins[i:i + per_chunk]
                      for i in range(0, len(wins), per_chunk)]
            for chunk in chunks:
                plan.append((wclass, m_est, per_chunk, len(chunks), chunk))
        # the fused two-pass EM dispatch refits from its own windows'
        # samples, so it is only equivalent to the global host refit when
        # one dispatch covers the whole solve (the common case — the
        # dispatch planner merges aggressively for exactly this reason)
        use_fused = fused and len(plan) == 1
        if use_fused:
            stats["fused_em_applied"] = 1.0

        pending = []
        for wclass, m_est, per_chunk, n_chunks, chunk in plan:
            t0 = _time.perf_counter()
            packed = pack_problem(
                in_spans, out_span_partitions, out_eps, dists, in_ep, dag,
                force_skip_ids=force_skip_ids, parallel=parallel,
                windows=chunk, pad_w=wclass,
                pad_b=(per_chunk if n_chunks > 1 else n_dev
                       if n_dev > 1 else None),
                pad_m=m_est if n_chunks > 1 else None,
                ranges=ranges_all[[row_of[w] for w in chunk]],
                skip_caps=skip_caps_all[[row_of[w] for w in chunk]],
                in_cols=in_cols, out_cols=out_cols,
            )
            _stat_add(stats, "pack_s", _time.perf_counter() - t0)
            a = packed.arrays
            if mesh is not None:
                from traceweaver_tpu.parallel.mesh import put_sharded

                a = put_sharded(a, mesh)
            B_c, W_c = a["in_start"].shape
            M_c = a["out_start"].shape[2]
            K_c = a["in_wt"].shape[1]
            # static neighbour bounds: tightest power-of-two cover of the
            # DAG's max in/out degree, so the score build only evaluates
            # real DAG edges (in-degree ~1 here) instead of all E
            pm_np = packed.arrays["pred_mask"]
            mp = _bucket(max(1, int(pm_np.sum(axis=1).max(initial=0))),
                         minimum=1)
            ms = _bucket(max(1, int(pm_np.sum(axis=0).max(initial=0))),
                         minimum=1)
            n_pred, n_succ = min(mp, E), min(ms, E)
            # analytic op accounting for utilization estimates:
            # score build ~ (n_pred+n_succ+2) mixture evals of K comps
            # (~8 flops each) per cell; Sinkhorn 2 LSE passes/iter
            # (~6 flops/cell); rounding ~log2(W) rounds (~8 flops/cell).
            # NOTE: an UPPER BOUND since the sweep loop and the Sinkhorn
            # iteration both exit early on convergence — derived MFU/HBM
            # figures are therefore upper bounds too
            n_passes = 2 if use_fused else 1
            cells = B_c * E * W_c * M_c * n_sweeps * n_passes
            _stat_add(stats, "flops_est", cells * (
                8.0 * K_c * (n_pred + n_succ + 2)
                + 6.0 * 2 * self.n_sinkhorn
                + 8.0 * max(1, W_c.bit_length())
            ))
            # XLA-path HBM traffic bound: the [W, M] score block streams
            # twice per Sinkhorn iteration (row+col LSE) at the SCORE
            # itemsize (bf16 halves this — the whole point of
            # TW_PRECISION); the Pallas kernel keeps it VMEM-resident and
            # only pays one score read plus the f32 plan/result write
            _stat_add(stats, "bytes_est_xla",
                      cells * float(itemsize) * 2 * self.n_sinkhorn)
            _stat_add(stats, "bytes_est_pallas",
                      cells * (float(itemsize) + 2 * 4.0))
            t0 = _time.perf_counter()
            solve_fn = solve_em_packed if use_fused else solve_windows_packed
            if mesh is None:
                # AOT-escape accounting for the per-service path (tier
                # "full" of the lattice, runtime/aot.py); numeric here —
                # the ordered shape ledger rides the fleet stats dict
                from traceweaver_tpu.runtime import aot as _aot

                if _aot.note_packed(
                        solve_fn.__name__, B_c, E, W_c, M_c, mp, ms,
                        n_sweeps, self.epsilon, self.n_sinkhorn,
                        self.sinkhorn_tol, self.precision):
                    _stat_add(stats, "aot_packed_misses", 1.0)
            with _obs_profile.annotate("tw:solve:dispatch"):
                out = solve_fn(
                    a["in_start"], a["in_end"], a["in_valid"],
                    a["out_start"], a["out_end"], a["out_valid"],
                    a["skip_cap"], a["force_skip"],
                    a["pred_mask"], a["root_mask"], a["is_last"],
                    a["edge_wt"], a["edge_mu"], a["edge_sd"],
                    a["in_wt"], a["in_mu"], a["in_sd"],
                    a["ret_wt"], a["ret_mu"], a["ret_sd"],
                    epsilon=self.epsilon, n_sinkhorn=self.n_sinkhorn,
                    n_sweeps=n_sweeps, sinkhorn_tol=self.sinkhorn_tol,
                    max_preds=mp, max_succs=ms, precision=self.precision,
                    pallas=mesh_allows_pallas(mesh),
                )
            _stat_add(stats, "dispatch_s", _time.perf_counter() - t0)
            pending.append((packed, out))

        for _, out in pending:
            try:
                out.copy_to_host_async()
            except AttributeError:  # plain np.ndarray under some backends
                pass

        results = []
        t0 = _time.perf_counter()
        for packed, out in pending:
            # twlint: disable=TW003 — ledgered fetch site: the whole
            # loop is billed to wait_s below (the copy_to_host_async
            # pass above started every transfer; fleet-path fetches go
            # through fleet._fetch instead)
            o = np.asarray(out)
            ch = _layout.split_packed(o)
            results.append((packed, (ch["assign"], ch["topk_cols"],
                                     ch["not_best"], ch["feas"])))
        _stat_add(stats, "wait_s", _time.perf_counter() - t0)
        return results

    @staticmethod
    def _decode(packed: PackedProblem, assign: np.ndarray,
                topk_cols: np.ndarray, all_assignments, all_topk,
                index) -> List[np.ndarray]:
        """Device indices -> wire-format assignment dicts (merged in place).

        Whole-array passes per endpoint: each span's chosen column and
        its top-K columns become integer keys (:func:`_candidate_keys`,
        or :data:`NA_KEY` / :data:`SKIP_KEY`), the chosen key moves to
        the front of its top-K row (its first match dropped), the ids are
        gathered into one ``[n, K]`` object array, and one ``.tolist()``
        gives every span its list. ``index`` is the batch's
        :func:`window_index`.

        Returns each endpoint's chosen keys ``[n]`` in index order, for
        :meth:`_resolve_contested`.
        """
        w_of, i_of, pos = index
        M = packed.M
        K = topk_cols.shape[3]
        # 0-d object holders let tuple sentinels assign under boolean masks
        skip_v = np.empty((), dtype=object)
        skip_v[()] = SKIP
        na_v = np.empty((), dtype=object)
        na_v[()] = NA

        if isinstance(packed.in_ids, np.ndarray):
            # columnar: the id column gathers by position in one step
            span_ids = packed.in_ids[pos].tolist()
        else:
            span_ids = [packed.in_ids[p] for p in pos]
        t = np.arange(K - 1)
        chosen_keys = []
        for e, ep in enumerate(packed.out_eps):
            # column 0 the chosen column, then the top-K: a chosen column
            # past M clips to the last slot, a top-K one is NA
            raw = np.concatenate([assign[w_of, e, i_of][:, None],
                                  topk_cols[w_of, e, i_of, :]], axis=1)
            key, table = _candidate_keys(packed.out_ids[e], M, w_of[:, None],
                                         np.clip(raw, 0, M - 1))
            key[raw < 0] = NA_KEY
            key[:, 1:][raw[:, 1:] > M] = NA_KEY
            key[raw == M] = SKIP_KEY
            chosen, tk = key[:, 0], key[:, 1:]
            # [chosen] + top-K without its first match of chosen, cut to K
            match = tk == chosen[:, None]
            cut = np.where(match.any(axis=1), match.argmax(axis=1), K - 1)
            row = np.concatenate(
                [chosen[:, None],
                 np.take_along_axis(tk, t + (t >= cut[:, None]), axis=1)],
                axis=1)
            ids = np.empty(row.shape, dtype=object)
            real = row >= 0
            ids[real] = table[row[real]]
            ids[row == NA_KEY] = na_v
            ids[row == SKIP_KEY] = skip_v
            all_assignments[ep].update(zip(span_ids, ids[:, 0].tolist()))
            all_topk[ep].update(zip(span_ids, ids.tolist()))
            chosen_keys.append(chosen)
        return chosen_keys

    @staticmethod
    def _resolve_cross_window_duplicates(all_assignments, all_topk, in_ids,
                                         skip_budget) -> int:
        """Restore global one-to-one-ness across capped sub-windows.

        Perfect-cut segments are solved whole, so duplicates can only arise
        when a segment longer than ``max_window`` was split and two
        sub-windows both claimed an outgoing span from their (overlapping)
        candidate ranges. Per contested out-span, the earliest incoming
        span in time order (``in_ids`` order — the serial-peel convention)
        keeps it; only the losers are reassigned, to their best-ranked
        top-K alternative that no row (winner or not) holds, taking SKIP
        only while the endpoint's global ``|in| - |out|`` budget
        (traceweaver_v3.py:972) has room, else NA.

        Works on any dicts, however many batches filled them; returns the
        number of contested out-spans. :meth:`_resolve_contested` gives
        the same answers for one columnar batch from its integer keys.
        """
        n_contested = 0
        for ep, assign_map in all_assignments.items():
            claims, skips_used = _claims_in_order(assign_map, in_ids)
            contested = [c for c in claims.values() if len(c) > 1]
            n_contested += len(contested)
            _reassign_losers(assign_map, all_topk.get(ep, {}), in_ids,
                             contested, set(claims), skips_used,
                             skip_budget.get(ep, 0))
        return n_contested

    @staticmethod
    def _resolve_contested(packed: PackedProblem, chosen_keys, index,
                           in_ids, all_assignments, all_topk,
                           skip_budget) -> Tuple[np.ndarray, int]:
        """:meth:`_resolve_cross_window_duplicates` for the dicts one
        batch filled (:meth:`_decode` and its ``chosen_keys``): the rule
        runs only on the out-spans claimed more than once, which it
        alone changes.

        On the columnar path equal keys are the same out-span, so one
        ``bincount`` finds the contested ones and the SKIP count is one
        vector count; the object path's keys are batch slots, so it
        takes the dict loop. Returns each span's "some endpoint is NA"
        flag after the rule, in index order, and the number of contested
        out-spans.
        """
        pos = index[2]
        row_of = np.empty(len(in_ids), dtype=np.int64)
        row_of[pos] = np.arange(pos.size)
        unassigned = np.zeros(pos.size, dtype=bool)
        n_contested = 0
        for e, ep in enumerate(packed.out_eps):
            chosen = chosen_keys[e]
            na = chosen == NA_KEY
            assign_map, topk_map = all_assignments[ep], all_topk[ep]
            col = packed.out_ids[e]
            if isinstance(col, EndpointIds):
                real = np.flatnonzero(chosen >= 0)
                counts = np.bincount(chosen[real])
                hot = real[counts[chosen[real]] > 1]
                hot = hot[np.argsort(pos[hot], kind="stable")]
                claims: Dict = {}
                for p, k in zip(pos[hot].tolist(), chosen[hot].tolist()):
                    claims.setdefault(k, []).append(p)
                used = set(col.table[np.flatnonzero(counts)].tolist()) \
                    if claims else set()
                skips_used = int(np.count_nonzero(chosen == SKIP_KEY))
                contested = list(claims.values())
            else:
                claims, skips_used = _claims_in_order(assign_map, in_ids)
                contested = [c for c in claims.values() if len(c) > 1]
                used = set(claims)
            n_contested += len(contested)
            for p in _reassign_losers(assign_map, topk_map, in_ids,
                                      contested, used, skips_used,
                                      skip_budget.get(ep, 0)):
                na[row_of[p]] = assign_map[in_ids[p]] == NA
            unassigned |= na
        return unassigned, n_contested

    @staticmethod
    def _decode_batches(batches, out_eps, in_ids, skip_budget,
                        conf_arrs=None):
        """The per-service decode: every batch of one problem into one
        set of dicts, then the duplicate rule over all of them. Batches
        share no integer key space, so the rule takes the dict loop.
        Returns the dicts, each span's not-best flag and candidate count,
        and the number of contested out-spans; the quality reductions go
        to ``conf_arrs`` when given."""
        all_assignments = {ep: {} for ep in out_eps}
        all_topk = {ep: {} for ep in out_eps}
        # confidence: a span is "not best" if OT overrode the row argmax
        span_not_best = np.zeros(len(in_ids), dtype=bool)
        span_cands = np.ones(len(in_ids), dtype=np.int64)
        for packed, (assign, topk_cols, not_best, feas) in batches:
            index = window_index(packed.windows)
            WeaverTPU._decode(packed, assign, topk_cols,
                              all_assignments, all_topk, index)
            scatter_window_span_stats(index, not_best, feas,
                                      span_not_best, span_cands)
            if conf_arrs is not None:
                _quality.scatter_confidence(index, not_best, feas,
                                            topk_cols, conf_arrs)
        n_contested = WeaverTPU._resolve_cross_window_duplicates(
            all_assignments, all_topk, in_ids, skip_budget)
        return all_assignments, all_topk, span_not_best, span_cands, \
            n_contested

    # -- plugin entry point ------------------------------------------------
    def FindAssignments(self, method, process, in_span_partitions,
                        out_span_partitions, parallel, instrumented_hops,
                        true_assignments, invocation_graph=None,
                        true_skips: bool = False, true_dist: bool = False):
        assert len(in_span_partitions) == 1
        in_ep, in_spans = next(iter(in_span_partitions.items()))
        in_spans = sorted(in_spans, key=lambda s: (s.start_mus, s.end_mus))
        out_eps = self._topo_out_eps(out_span_partitions, invocation_graph)
        parallel_mode = parallel or method == "MaxScoreBatchParallelWithoutIterations"

        plan = plan_find_assignments(
            in_span_partitions, out_span_partitions, out_eps,
            invocation_graph, true_assignments,
            score_mode=self.score_mode, true_skips=true_skips,
            true_dist=true_dist, parallel_mode=parallel_mode,
        )
        n_in = plan["n_in"]
        skip_budget = plan["skip_budget"]
        force_skip_ids = plan["force_skip_ids"]
        dists = plan["dists"]
        iterations = plan["iterations"]

        import time as _time

        self.stats = {}
        all_assignments = all_topk = None
        not_best_count = 0
        per_span_candidates: Dict = {}
        in_ids = [s.GetId() for s in in_spans]
        it = 0
        while it < iterations:
            batches = self._solve_once(
                in_spans, out_span_partitions, out_eps, dists, in_ep,
                invocation_graph, force_skip_ids, parallel_mode,
                # fused on-device refit fits GMMs; the KDE score mode's
                # binned-KDE refit stays on the host two-pass path
                fused=(iterations == 2 and it == 0
                       and self.score_mode == "mixture"),
            )
            if self.stats.get("fused_em_applied"):
                # the single fused dispatch already ran refit + pass 2
                iterations = 1
            t0 = _time.perf_counter()
            conf_on = _quality.conf_enabled()
            conf_arrs = _quality.new_span_arrays(n_in) if conf_on else None
            (all_assignments, all_topk, span_not_best, span_cands,
             n_contested) = self._decode_batches(
                 batches, out_eps, in_ids, skip_budget, conf_arrs)
            not_best_count = int(span_not_best.sum())
            per_span_candidates = dict(zip(in_ids, span_cands.tolist()))
            self.per_span_confidence = (_quality.confidence_records(
                in_ids, _quality.finish_confidence(conf_arrs))
                if conf_on else {})
            _stat_add(self.stats, "decode_contested", n_contested)
            _stat_add(self.stats, "decode_s", _time.perf_counter() - t0)
            if it + 1 < iterations:
                t0 = _time.perf_counter()
                dists = timing.refit_from_assignments(
                    in_span_partitions, out_span_partitions,
                    invocation_graph, all_assignments, self.all_spans,
                    score_mode=self.score_mode,
                )
                _stat_add(self.stats, "refit_s",
                          _time.perf_counter() - t0)
            it += 1

        cnt_unassigned = sum(
            1
            for in_id in in_ids
            if any(all_assignments[ep][in_id] == NA for ep in out_eps)
        )

        return (all_assignments, all_topk, not_best_count, n_in,
                per_span_candidates, cnt_unassigned)
