#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, on the chip.

    python3 benchmarks/chip/run.py --workload enzian_zipf_closed \\
        --seed 7 --seconds 30 --trace 0

Prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and,
with ``--trace 1``, ``breakdown``), followed by ``checks``: every number
the correctness check compared, beside its limit, which are also the
last lines on standard error.  With ``--trace 0`` the metrics are the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics.
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def require_chips(n: int):
    """The devices of a TPU host with at least ``n`` chips, or exit."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"run.py: JAX found no TPU (platform "
                 f"{devices[0].platform!r}); the benchmark runs on the "
                 f"chip only")
    if len(devices) < n:
        sys.exit(f"run.py: the cell needs {n} chips, JAX sees "
                 f"{len(devices)}")
    return devices


def peak_table(kind: str) -> dict:
    """The device's published peaks; a device missing from the table is
    an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        sys.exit(f"run.py: no peaks for device kind {kind!r} in "
                 f"peaks.json")
    return table[kind]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    import harness
    cell = harness.load_cell(args.workload)
    devices = require_chips(cell.chips)
    peaks = peak_table(devices[0].device_kind)
    harness.enable_compile_cache()
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), devices[:cell.chips], T0,
                              hbm_bytes=peaks["hbm_bytes"])
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
