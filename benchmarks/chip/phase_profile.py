#!/usr/bin/env python3
"""Split one cell's stream program by phase, on the chip.

    python3 benchmarks/chip/phase_profile.py --workload enzian_zipf_closed \\
        --seed 7 --seconds 51

Runs the cell as ``run.py --trace 1`` does (set-up with one warm-up
point, then points back to back for about ``--seconds`` under the
profiler, between the same ``bench.*`` spans), takes the stream
program's compiled HLO from ``driver.stream_program`` after set-up, and
prints one JSON line: the cell's per-layer metrics as ``run.py --trace
1`` reads them, the per-phase numbers of ``phase_reduce`` (``phases``,
``idle_phases``, the top ops of each phase, the split's identity), and
the set-up and window times.
It checks no result (``run.py`` does), and without a TPU it exits
non-zero and prints nothing.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import harness  # noqa: E402
import phase_reduce  # noqa: E402
import trace_reduce  # noqa: E402
import traffic_gen  # noqa: E402


def stream_hlo(target: harness.Target, inputs: traffic_gen.Inputs) -> str:
    """The compiled HLO text of the program ``target.run(inputs)`` runs
    (a cache hit once that program has run)."""
    from repro.traffic import ArrivalSchedule, Workload, stream_program
    arr = (None if inputs.arrival is None
           else ArrivalSchedule(inputs.arrival))
    cfg = target._stream(workload=Workload(inputs.op, inputs.line,
                                           inputs.value), arrivals=arr)
    fn, operands = stream_program(target.engine, cfg)
    return fn.lower(*operands).compile().as_text()


def profile(cell: harness.Cell, seed: int, seconds: float,
            t0: float) -> dict:
    """Set-up, HLO, traced window and reduction of one run."""
    import jax
    from jax.profiler import TraceAnnotation
    clock = harness.CompileClock()
    harness.check_interface()
    target = harness.Target(cell.config, cell.traffic)
    (warm,) = target.inputs(seed, -1)
    harness.run_point(target, [warm])
    setup_s = time.perf_counter() - t0
    t = time.perf_counter()
    phase_of = phase_reduce.hlo_phases(stream_hlo(target, warm))
    hlo_s = time.perf_counter() - t

    tmp = tempfile.mkdtemp(prefix="chipbench-phases-")
    try:
        jax.profiler.start_trace(tmp,
                                 profiler_options=harness._profile_options())
        points = []
        with TraceAnnotation(trace_reduce.WINDOW_SPAN):
            start = time.perf_counter()
            count = None
            while count is None or len(points) < count:
                with TraceAnnotation("bench.generate"):
                    inputs = target.inputs(seed, len(points))
                points.append(harness.run_point(target, inputs))
                if count is None:
                    count = max(1, round(seconds
                                         / (time.perf_counter() - start)))
            end = time.perf_counter()
        jax.profiler.stop_trace()
        found = sorted(Path(tmp).rglob("*.xplane.pb"))
        planes = trace_reduce.load(str(found[-1])) if found else []
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    base = trace_reduce.summarize(planes)
    win = harness.Window(points, start, end, clock.count(start, end), base)
    phases = phase_reduce.summarize(planes, phase_of, base)
    steps = sum(p.steps for p in points)
    out = {"cell": cell.name, "seed": seed, "points": len(points),
           "steps": steps, "window_s": end - start, "setup_s": setup_s,
           "hlo_s": hlo_s, "compiles_in_window": win.compiles,
           "metrics": {k: v["value"]
                       for k, v in harness.per_layer(cell, win).items()}}
    found_metrics = phase_reduce.metrics(phases, steps)
    if found_metrics:
        out["metrics"].update(found_metrics)
        out["identity"] = phase_reduce.identity(phases, steps)
        out["breakdown"] = dict(trace_reduce.breakdown(base),
                                **phase_reduce.breakdown(phases))
        out["phase_ops"] = phases.phase_ops
        out["point_host"] = phases.point_host
        out["top_ops_phase"] = [
            [name, phase_of.get(name.split(" ")[0])]
            for name, _ in base.device_ops]
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()

    import run
    cell = harness.load_cell(args.workload)
    if "fleet" in cell.config:
        sys.exit("phase_profile.py: the program gives the stream program "
                 "of run_stream only (driver.stream_program), not a "
                 "fleet's")
    run.require_chips(cell.chips)
    harness.enable_compile_cache()
    print(json.dumps(profile(cell, args.seed, args.seconds, T0)),
          flush=True)


if __name__ == "__main__":
    main()
