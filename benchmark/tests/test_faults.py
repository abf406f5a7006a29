"""A run whose timed path is broken underneath must read ``correct: false``.

Each test drives the batch driver on the CPU at a small size (no look
for a chip), with one fault planted in the program's ``solve_fleet``:

- half of the batch left out: the solve sees every other service only;
- answers altered where they are produced: the first service's first
  endpoint hands each incoming span its neighbour's call.

A fault a batch cell cannot have is not planted: a solve has no state
that a step could return unchanged (the corpus is re-solved whole every
round), and a one-chip cell has no exchange between chips.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402


CELLS = [w["name"] for w in harness.benchmark_spec(ROOT)["workloads"]]


@pytest.fixture(params=CELLS)
def small_run(request, tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "WORK_DIR", str(tmp_path))
    import jax

    def run(seed: int = 2 ** 31 + 99):
        spec = harness.resolve_cell(request.param)
        spec["traffic"] = dict(spec["traffic"], requests_per_round=400)
        return harness.driver(spec["config"]).run(
            spec, seed=seed, seconds=0.5, trace=False,
            t0=time.monotonic(), jax=jax)

    return run


def _patch_solve(monkeypatch, wrap):
    from traceweaver_tpu.algorithms import fleet

    real = fleet.solve_fleet
    monkeypatch.setattr(fleet, "solve_fleet",
                        lambda items, **kw: wrap(real, items, **kw))


def test_sound_run_is_correct(small_run):
    out = small_run()
    assert out["correct"], out["checks"]
    assert out["checks"]["wrong_trace_pct"]["value"] == 0.0


def test_half_the_batch_left_out(small_run, monkeypatch):
    def half(real, items, **kw):
        outs = real(items[::2], **kw)
        full = [None] * len(items)
        full[::2] = outs
        return full

    _patch_solve(monkeypatch, half)
    out = small_run()
    assert not out["correct"]
    assert out["failed"] > 0


def test_answer_altered_where_produced(small_run, monkeypatch):
    def rotate(real, items, **kw):
        outs = real(items, **kw)
        assign = next(iter(outs[0][0].values()))
        keys = list(assign)
        vals = [assign[k] for k in keys]
        assign.update(zip(keys, vals[1:] + vals[:1]))
        return outs

    _patch_solve(monkeypatch, rotate)
    out = small_run()
    assert not out["correct"]
    assert out["checks"]["wrong_trace_pct"]["value"] > 0
