"""Each batch cell's control comes out as not correct.

The control a cell's workload file names (``checks/controls.py``) is
read through the cell's own comparison at a size a test run holds
(2000 requests): ``nearest_start`` (no one-to-one matching) fails the
150 requests/s cell's limit, and ``swapped_links`` fails the 25
requests/s cell's exact comparison.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.tools.readings import control_reading  # noqa: E402

SPEC = harness.benchmark_spec(ROOT)


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_control_fails_the_cell(cell):
    spec = harness.resolve_cell(cell, SPEC)
    spec["traffic"] = dict(spec["traffic"], requests_per_round=2000)
    limit = spec["workload"]["limits"]["wrong_trace_pct"]
    for seed in (2 ** 31 + 3, 2 ** 31 + 4, 2 ** 31 + 5):
        got = control_reading(spec, seed)
        assert not got["correct"]
        assert got["checks"]["wrong_trace_pct"] > limit
