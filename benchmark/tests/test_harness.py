"""Tests of the benchmark harness that need no chip.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

SPEC = harness.benchmark_spec(ROOT)


def _tree_digest(root: str) -> str:
    h = hashlib.sha256()
    for dirpath, _, files in sorted(os.walk(root)):
        for name in sorted(files):
            path = os.path.join(dirpath, name)
            h.update(os.path.relpath(path, root).encode())
            with open(path, "rb") as f:
                h.update(f.read())
    return h.hexdigest()


def _small(cell: str, requests: int = 400):
    spec = harness.resolve_cell(cell, SPEC)
    spec["traffic"] = dict(spec["traffic"], requests_per_round=requests)
    return spec


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_entry_resolves_to_its_files(cell):
    spec = harness.resolve_cell(cell, SPEC)
    assert harness.driver(spec["config"]).run
    gen = harness.generator(spec["traffic"])
    assert gen.draw_traces and gen.due_answers and gen.write_corpus
    assert "wrong_trace_pct" in spec["workload"]["limits"]
    for m in spec["end_to_end"] + spec["per_layer"]:
        path = os.path.join(harness.BENCH_DIR, "metrics", m["name"] + ".py")
        assert harness.load_module(path, "m").read
    names = [m["name"] for m in spec["end_to_end"]]
    assert "setup_s" in names and len(names) >= 2
    assert spec["per_layer"]


def test_config_files_are_their_own():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(set(files)) == len(files)
    for c in SPEC["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["name"] == c["name"]


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_generator_is_byte_deterministic_per_seed(cell, tmp_path):
    spec = _small(cell)
    gen = harness.generator(spec["traffic"])
    seed = 2 ** 31 + 12345
    a = gen.write_corpus(gen.draw_traces(spec["config"], spec["traffic"],
                                         seed), str(tmp_path / "a"))
    b = gen.write_corpus(gen.draw_traces(spec["config"], spec["traffic"],
                                         seed), str(tmp_path / "b"))
    assert len(a) == len(b) == len(spec["config"]["request_types"])
    assert _tree_digest(str(tmp_path / "a")) == _tree_digest(str(tmp_path / "b"))
    other = gen.draw_traces(spec["config"], spec["traffic"], seed + 1)
    same = gen.draw_traces(spec["config"], spec["traffic"], seed)
    # another seed: the same requests of each type in another order
    assert other != same
    assert gen.span_count(other) == gen.span_count(same)
    assert [len(t) for t in other] == [len(t) for t in same]
    assert [len(t[0]) for t in other] == [len(t[0]) for t in same]


def test_due_answers_link_each_call_to_its_caller():
    spec = _small(SPEC["workloads"][0]["name"], requests=20)
    gen = harness.generator(spec["traffic"])
    graphs = gen.draw_traces(spec["config"], spec["traffic"], 7)
    due = gen.due_answers(graphs)
    for calls in graphs[0]:
        tid = calls[0][0]
        assert len(due[tid]) == len(calls) - 1
        for (in_tid, parent), (out_tid, rpc) in due[tid]:
            assert in_tid == out_tid == tid
            assert rpc.rsplit(".", 1)[0] == parent


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         SPEC["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_peaks_table_names_its_source_and_the_v5e():
    with open(os.path.join(harness.BENCH_DIR, "peaks.json")) as f:
        peaks = json.load(f)
    assert "TPU v5e" in peaks["source"]
    v5e = peaks["by_device_kind"]["TPU v5 lite"]
    assert v5e["flops_bf16"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9


def test_run_exits_nonzero_without_the_program(tmp_path):
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in SPEC["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable] + SPEC["command"][1:] + [
            "--workload", SPEC["workloads"][0]["name"], "--seed", "1",
            "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
