#!/usr/bin/env python3
"""A fleet point against its members' solo runs, on the chips.

    python3 benchmarks/chip/fleet_solo.py --workload <fleet cell> --seed 7

Runs point 0 of a fleet cell as the benchmark does (one ``run_fleet``
call), then each member alone through ``run_stream``, on the chip that
held it in the fleet, with the same generated inputs and the fleet's step
budget.  Prints one JSON line per member: whether its counters, message
counts, retirement trace and final state are bit-identical to the solo
run's (the state's rows past the member's own remotes, which the fleet
pads, have to be all zero), with each chip's memory in use once the
fleet's members are read back, and its peak.  The last line
says whether every member was.  Without a TPU, or with fewer chips than
the cell asks for, it exits non-zero and prints nothing.  The
benchmark's own runs do not run this.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import harness  # noqa: E402

sys.path.insert(0, str(harness.CHECKOUT))
from chip_smoke import assert_identical  # noqa: E402


def differences(fleet_run, solo_run, remotes: int) -> list:
    """What differs between a fleet member's run and its solo run: the
    first of the counters, message counts and retirement trace that does
    (``chip_smoke.assert_identical``), and every state leaf that does; a
    leaf that the fleet pads is compared on the member's own rows, and
    its padded rows have to be zero."""
    import jax
    import jax.numpy as jnp
    bad = []
    try:
        assert_identical(fleet_run, solo_run, "fleet member")
    except (AssertionError, RuntimeError) as e:
        bad.append(str(e))
    flat_f = jax.tree_util.tree_flatten_with_path(fleet_run.state)[0]
    flat_s = jax.tree_util.tree_leaves(solo_run.state)
    for (path, a), b in zip(flat_f, flat_s):
        name = "state" + jax.tree_util.keystr(path)
        if a.shape != b.shape:    # padded to the fleet's largest member
            if bool(jnp.any(a[remotes:] != 0)):
                bad.append(name + "[padded rows]")
            a = a[:remotes]
        if a.shape != b.shape or not bool(jnp.array_equal(a, b)):
            bad.append(name)
    return bad


def compare(cell: harness.Cell, seed: int, devices) -> list:
    """One line per member of point 0 of ``cell`` with ``seed``."""
    import jax
    from repro.traffic import StreamConfig, Workload, run_stream
    target = harness.make_target(cell)
    inputs = target.inputs(seed, 0)
    t = time.perf_counter()
    runs = target.run(inputs)
    jax.block_until_ready([r.state for r in runs])
    fleet_s = time.perf_counter() - t
    steps = int(runs[0].counters.steps)
    peaks_fleet = [harness.peak_bytes([d]) for d in devices]
    in_use = [int((d.memory_stats() or {}).get("bytes_in_use", 0))
              for d in devices]

    def solo(m):
        inp, eng = inputs[m], target.engines[m]
        device = next(iter(runs[m].state.dir.backing.devices()))
        with jax.default_device(device):
            t = time.perf_counter()
            run = run_stream(eng.build(), StreamConfig(
                workload=Workload(inp.op, inp.line, inp.value),
                width=int(cell.traffic.get("width", 1)), steps=steps,
                collect_trace=True))
            jax.block_until_ready(run.state)
            return run, time.perf_counter() - t, device

    lines = []
    with ThreadPoolExecutor(len(runs)) as pool:
        for m, (run, solo_s, device) in enumerate(
                pool.map(solo, range(len(runs)))):
            remotes = target.member_remotes[m]
            bad = differences(runs[m], run, remotes)
            lines.append({"member": m, "remotes": remotes,
                          "device": str(device), "steps": steps,
                          "retired": int(np.sum(run.counters.retired)),
                          "msg_total": int(np.sum(run.msg_count)),
                          "bit_identical": not bad, "differs_in": bad,
                          "fleet_s": fleet_s, "solo_s": solo_s})
            del run
    peaks = [harness.peak_bytes([d]) for d in devices]
    for line in lines:
        line.update(peak_bytes_after_fleet=peaks_fleet,
                    bytes_in_use_after_fleet=in_use, peak_bytes=peaks)
    return lines


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args()

    from run import require_chips
    cell = harness.load_cell(args.workload)
    if "fleet" not in cell.config:
        sys.exit(f"fleet_solo.py: {cell.name} runs no fleet")
    devices = require_chips(cell.chips)[:cell.chips]
    harness.enable_compile_cache()
    lines = compare(cell, args.seed, devices)
    for line in lines:
        print(json.dumps(dict(line, cell=cell.name, seed=args.seed)),
              flush=True)
    print(json.dumps({"all_bit_identical": all(x["bit_identical"]
                                               for x in lines),
                      "seconds": time.perf_counter() - T0}), flush=True)


if __name__ == "__main__":
    main()
