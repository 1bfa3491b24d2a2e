#!/usr/bin/env python3
"""The readings a cell's correctness limits are set from, on the chip.

    python3 benchmarks/chip/readings.py --workload enzian_zipf_closed \\
        --seeds 201,202,203 --control-seeds 301,302,303 --seconds 30

In one process: a run of the program on each seed, then a run of the
control on each control seed (the program's own bfloat16 line-data path,
the precision below the configuration's float32; for a fleet, its fresh
state made in bfloat16), each a window of
``--seconds`` as the benchmark measures it.  Prints one JSON line per
run with every number the check compares.  The benchmark's own runs do
not run this.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = [int(s) for s in args.control_seeds.split(",") if s]

    import jax.numpy as jnp
    import harness
    from run import require_chips
    cell = harness.load_cell(args.workload)
    devices = require_chips(cell.chips)[:cell.chips]
    harness.enable_compile_cache()
    for kind, dtype, group in (("program", None, seeds),
                               ("control", jnp.bfloat16, controls)):
        for i, seed in enumerate(group):
            res = harness.run_cell(
                cell, seed, args.seconds, False, devices,
                time.perf_counter(), warm_up=i == 0,
                target_factory=lambda: harness.make_target(cell, dtype))
            print(json.dumps({"cell": cell.name, "run": kind, "seed": seed,
                              "correct": res["correct"],
                              "points": res["attempted"],
                              "checks": res["checks"],
                              "metrics": res["metrics"]}), flush=True)


if __name__ == "__main__":
    main()
