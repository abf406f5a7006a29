"""Read a cell's compared numbers over many seeds in one process.

    python3 benchmark/tools/readings.py --workload hotel-150rps \
        --seeds 11,12,13 --seconds 3 [--precision bf16] [--control] \
        [--timelines]

Each seed is one whole run of the cell's driver (set-up, a short
window, the comparison with the reference); the process keeps its
compiled programs between seeds, so a dozen seeds cost one set-up of
JAX. ``--precision bf16`` runs the program's own lower-precision score
path (``TW_PRECISION``). ``--timelines`` draws each seed's timeline from
the seed instead of the mix's ``timeline_seed``, so that the readings
cover as many timelines as seeds. ``--control`` reads the control its workload
file names instead of the program (``checks/controls.py``), at the
cell's size and through the same comparison. One JSON line per seed on stdout. Needs
the cell's TPU chips, like ``run.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402


def control_reading(spec, seed: int):
    """The control on one seed: its compared numbers and its verdict."""
    from benchmark.checks import callgraph_truth, controls
    from benchmark.drivers import batch

    gen = harness.generator(spec["traffic"])
    graphs = gen.draw_traces(spec["config"], spec["traffic"], seed)
    control = controls.CONTROLS[spec["workload"]["control"]]
    grade = callgraph_truth.grade(gen.due_answers(graphs), control(graphs))
    checks = batch.compare([grade], 0, spec["workload"]["limits"])
    return dict(correct=all(c["value"] <= c["limit"]
                            for c in checks.values()),
                checks={k: c["value"] for k, c in checks.items()},
                grade=grade)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=3.0)
    p.add_argument("--precision", choices=("f32", "bf16"), default="f32")
    p.add_argument("--control", action="store_true")
    p.add_argument("--timelines", action="store_true")
    args = p.parse_args(argv)

    spec = harness.resolve_cell(args.workload)
    harness.setup_env(False)
    os.environ["TW_PRECISION"] = args.precision
    jax = harness.init_jax()
    harness.check_devices(jax, int(spec["cell"]["chips"]))
    drv = harness.driver(spec["config"])
    traffic = spec["traffic"]
    for seed in (int(s) for s in args.seeds.split(",")):
        if args.timelines:
            spec["traffic"] = dict(traffic, timeline_seed=seed)
        if args.control:
            print(json.dumps(dict(workload=args.workload, seed=seed,
                                  control=spec["workload"]["control"],
                                  **control_reading(spec, seed))),
                  flush=True)
            continue
        out = drv.run(spec, seed=seed, seconds=args.seconds, trace=False,
                      t0=time.monotonic(), jax=jax)
        print(json.dumps(dict(
            workload=args.workload, seed=seed, precision=args.precision,
            correct=out["correct"],
            checks={k: c["value"] for k, c in out["checks"].items()},
            grade=out["measurement"]["grade_last"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
