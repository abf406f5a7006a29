"""Run one benchmark cell once and print its result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the cell's TPU chips.
The process holds the chip and runs the program in-process; the cell's
driver (``benchmark/drivers/``) sets it up, measures ``--seconds``, and
compares what the window produced with the benchmark's own reference.
The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each compared number with its limit.
No TPU, or fewer chips than the cell asks for: exit non-zero, no result.
"""

from __future__ import annotations

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    spec = harness.resolve_cell(args.workload)
    chips = int(spec["cell"]["chips"])
    harness.setup_env(bool(args.trace))
    jax = harness.init_jax()
    harness.check_devices(jax, chips)
    out = harness.driver(spec["config"]).run(
        spec, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t0=T0, jax=jax)
    entries = spec["per_layer"] if args.trace else spec["end_to_end"]
    result = dict(correct=bool(out["correct"]),
                  attempted=int(out["attempted"]),
                  failed=int(out["failed"]),
                  metrics=harness.read_metrics(entries, out["measurement"]),
                  device=out["device"])
    if args.trace and out.get("breakdown"):
        result["breakdown"] = out["breakdown"]
    harness.finish(result, out["checks"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
