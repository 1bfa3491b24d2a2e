"""Benchmark harness entry point: one function per paper table/figure plus
the roofline table from the dry-run artifacts.

    PYTHONPATH=src python -m benchmarks.run [--only fig5] [--roofline-dir D]

Prints ``name,us_per_call,derived`` CSV.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def roofline_rows(dryrun_dir: str):
    rows = []
    if not os.path.isdir(dryrun_dir):
        return [("roofline/missing", 0.0, f"no dir {dryrun_dir}")]
    for name in sorted(os.listdir(dryrun_dir)):
        if not name.endswith(".json"):
            continue
        with open(os.path.join(dryrun_dir, name)) as f:
            rec = json.load(f)
        cid = f"{rec['arch']}/{rec['shape']}/{rec['mesh']}"
        if rec.get("status") == "skipped":
            rows.append((f"roofline/{cid}", 0.0, "skipped: " +
                         rec["reason"][:60]))
            continue
        if rec.get("status") != "ok":
            rows.append((f"roofline/{cid}", 0.0,
                         "FAILED " + rec.get("error", "?")[:80]))
            continue
        # prefer the first-principles terms (the HLO-derived block counts
        # while-loop bodies once on the CPU backend — see EXPERIMENTS.md)
        r = rec.get("roofline_analytic") or rec["roofline"]
        rows.append((
            f"roofline/{cid}", 0.0,
            f"bottleneck={r['bottleneck']} frac={r['roofline_fraction']:.3f}"
            f" tC={r['t_compute']:.2e}s tM={r['t_memory']:.2e}s"
            f" tX={r['t_collective']:.2e}s"
            f" useful={r['useful_flops_fraction']:.2f}"))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--roofline-dir", default="experiments/dryrun")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks.paper_benches import ALL

    print("name,us_per_call,derived")
    for fn in ALL:
        if args.only and args.only not in fn.__name__:
            continue
        for name, us, derived in fn():
            print(f"{name},{us:.2f},\"{derived}\"", flush=True)
    if not args.only or "roofline" in args.only:
        for name, us, derived in roofline_rows(args.roofline_dir):
            print(f"{name},{us:.2f},\"{derived}\"", flush=True)


if __name__ == "__main__":
    main()
