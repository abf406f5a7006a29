"""Cell resolution, device checks and the result line.

Everything a cell needs is found by name from ``BENCHMARK.json``:

- the cell (``workloads``) names its configuration and traffic mix;
- ``benchmark/configs/<config>.json`` holds the deployment and names
  the driver (``benchmark/drivers/<driver>.py``) that runs it;
- ``benchmark/traffic/<traffic>.json`` holds the mix's parameters and
  names its generator (``benchmark/generators/<generator>.py``);
- ``benchmark/workloads/<cell>.json`` holds the cell's correctness
  limits and what it stands for;
- each metric is read by ``benchmark/metrics/<metric>.py``, whose
  ``read(measurement)`` returns a number or ``None`` (nothing to read:
  the metric is left out of the line).
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from types import ModuleType
from typing import Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
#: runtime outputs (corpora, traces, the compile cache): inside the
#: checkout at a fixed path, listed in .gitignore
WORK_DIR = os.path.join(ROOT, ".benchwork")
JAX_CACHE_DIR = os.path.join(WORK_DIR, "jax_cache")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark_spec(root: str = ROOT) -> Dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def resolve_cell(name: str, spec: Optional[Dict] = None) -> Dict:
    """The cell's entry, configuration, traffic, limits and metric names."""
    spec = spec or benchmark_spec()
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; cells: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config_entry = configs[cell["config"]]
    config = _load_json(os.path.join(ROOT, config_entry["file"]))
    traffic = _load_json(os.path.join(BENCH_DIR, "traffic",
                                      cell["traffic"] + ".json"))
    workload = _load_json(os.path.join(BENCH_DIR, "workloads",
                                       name + ".json"))

    def metrics_for(entries: List[Dict]) -> List[Dict]:
        return [m for m in entries
                if "workloads" not in m or name in m["workloads"]]

    return dict(cell=cell, config=config, traffic=traffic,
                workload=workload,
                end_to_end=metrics_for(spec["end_to_end"]),
                per_layer=metrics_for(spec["per_layer"]))


def driver(config: Dict) -> ModuleType:
    name = config["driver"]
    return load_module(os.path.join(BENCH_DIR, "drivers", name + ".py"),
                       f"bench_driver_{name}")


def generator(traffic: Dict) -> ModuleType:
    name = traffic["generator"]
    return load_module(os.path.join(BENCH_DIR, "generators", name + ".py"),
                       f"bench_gen_{name}")


def read_metrics(entries: List[Dict], measurement: Dict) -> Dict:
    """Each metric from its own reader; a reader's ``None`` leaves it out."""
    out = {}
    for m in entries:
        mod = load_module(os.path.join(BENCH_DIR, "metrics",
                                       m["name"] + ".py"),
                          "bench_metric_" + m["name"].replace(".", "_"))
        value = mod.read(measurement)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def setup_env(trace: bool) -> None:
    """Environment for the process that holds the chip; call before JAX
    is imported. The compile cache lives at a fixed path in the checkout,
    and so do the TPU runtime's logs."""
    os.makedirs(JAX_CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = JAX_CACHE_DIR
    os.environ["TPU_LOG_DIR"] = os.path.join(WORK_DIR, "tpu_logs")
    if trace:
        os.environ["TW_PROFILE"] = "1"  # the program's host stage spans
    else:
        os.environ.pop("TW_PROFILE", None)


def init_jax():
    """JAX with the program's persistent compile cache on: it keeps every
    program, however small, in the directory ``setup_env`` gave it."""
    import jax

    from traceweaver_tpu.runtime.jax_cache import (
        enable_persistent_compilation_cache,
    )

    enable_persistent_compilation_cache()
    return jax


def check_devices(jax, chips: int) -> None:
    """No TPU, or fewer chips than the cell asks for: stop, no result."""
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise SystemExit(
            f"benchmark needs {chips} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s)")


class CompileCounter:
    """Backend compiles, and persistent-cache hits, seen by JAX's
    monitoring events."""

    def __init__(self) -> None:
        from jax._src import monitoring

        self.count = 0
        self.cache_hits = 0

        def on_duration(name, secs, **kw):
            if name == "/jax/core/compile/backend_compile_duration":
                self.count += 1

        def on_event(name, **kw):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)


def device_entry(jax, chips: int) -> Dict:
    devs = jax.devices()[:chips]
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}  # None where a backend keeps none
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return dict(platform=devs[0].platform, kind=devs[0].device_kind,
                count=len(devs), memory_peak_bytes=max(peaks))


def finish(result: Dict, checks: Dict[str, Dict]) -> None:
    """Print the compared numbers as the last lines on stderr, then the
    result as the last line of stdout, with ``checks`` as its last key."""
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    result = dict(result)
    result["checks"] = checks
    print(json.dumps(result), flush=True)
