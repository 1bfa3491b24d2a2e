#!/usr/bin/env python3
"""Run the streaming coherence engine on a TPU, in one process.

    python chip_smoke.py             # one chip: stream_xla, stream_pallas,
                                     # stream_packed
    python chip_smoke.py --chips 4   # four chips: the sharded fleet and
                                     # the solo runs it is compared with

The deployment is the paper's own platform (Enzian): the FPGA-side home
directory of a 48-core Cavium ThunderX-1 socket whose 16 MB shared L2
holds 131,072 lines of 128 bytes — R=48 caching agents, L=131,072 lines,
each line 32 float32 (``block=32``) — driven by a zipfian stream of 16
ops per remote (seed 0) with the step budget of ``default_steps``.

Every phase goes through the user-facing surface (``EngineConfig`` /
``StreamConfig`` -> ``run_stream``, ``FleetConfig`` -> ``run_fleet``),
checks its results (the oracle replay of ``validate_run``, or bit-identity
with the run it is compared with) and prints one JSON line.  The last line
is ``{"ok": true, "device": {...}}``.  Without a TPU, or when any phase
fails, the script exits non-zero and prints no ``ok`` line.

``compile_s`` is the time JAX itself records for tracing, lowering and
compiling (a persistent-cache hit counts its retrieval); ``wall_s`` is
the host clock around the call, which returns only once the device has
finished and its results are on the host, less ``compile_s``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

REMOTES, LINES, BLOCK = 48, 131_072, 32
OPS, SEED = 16, 0
#: the four-chip fleet: one full-size member per chip.
FLEET_REMOTES = (8, 16, 32, 48)
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """JAX's compile-duration events, from every thread, as intervals on
    the host clock; nested events (a kernel traced while its caller
    lowers) and concurrent ones count once."""

    def __init__(self):
        import jax
        self.spans = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name in COMPILE_EVENTS:
            end = time.perf_counter()
            self.spans.append((end - secs, end))

    def timed(self, fn):
        """(fn(), compile_s, wall_s) for one blocking call."""
        n0, t0 = len(self.spans), time.perf_counter()
        out = fn()
        wall = time.perf_counter() - t0
        compile_s, reach = 0.0, t0
        for start, end in sorted(self.spans[n0:]):
            start = max(start, reach)
            if end > start:
                compile_s += end - start
                reach = end
        return out, compile_s, wall - compile_s


def check(ok: bool, what: str) -> None:
    """A failed check ends the run (``assert`` would vanish under -O)."""
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def require_tpu(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devs[0].platform!r}); this script runs on the chip only")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} but only {len(devs)} TPU "
                 f"device(s) are visible")
    return devs


def peak_bytes(dev) -> int:
    return int((dev.memory_stats() or {}).get("peak_bytes_in_use", -1))


def stream_cfg(steps: int = 0, trace: bool = True):
    from repro.traffic import StreamConfig, WorkloadSpec
    return StreamConfig(WorkloadSpec("zipfian", ops=OPS, seed=SEED),
                        steps=steps, collect_trace=trace)


def summary(run) -> dict:
    return {"steps": int(run.counters.steps),
            "ops_retired": int(run.counters.retired.sum()),
            "completed": bool(run.completed),
            "msg_total": int(run.msg_count.sum()),
            "msg_count": [int(x) for x in run.msg_count],
            "payload_msgs": int(run.payload_msgs)}


def assert_identical(a, b, what: str) -> None:
    """Counters, message counts and (when traced) the retirement trace of
    two runs agree bit for bit."""
    import numpy as np
    np.testing.assert_array_equal(a.msg_count, b.msg_count,
                                  err_msg=f"{what}: msg_count")
    check(a.payload_msgs == b.payload_msgs, f"{what}: payload_msgs")
    for f, x, y in zip(a.counters._fields, a.counters, b.counters):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"{what}: counters.{f}")
    if a.trace is not None or b.trace is not None:
        np.testing.assert_array_equal(a.trace.retire_step,
                                      b.trace.retire_step,
                                      err_msg=f"{what}: retirement trace")


def fit_lines(dev, clock) -> "tuple[int, dict]":
    """The directory size the XLA stream program fits at: L=131,072
    unless the compiled program's own memory analysis exceeds the chip,
    in which case L halves (R, block and the workload never change)."""
    from repro.traffic import EngineConfig, stream_program
    limit = int((dev.memory_stats() or {}).get("bytes_limit", 0))
    lines, cuts = LINES, []
    while True:
        prog, operands = stream_program(
            EngineConfig(remotes=REMOTES, lines=lines, block=BLOCK).build(),
            stream_cfg())
        ma, compile_s, _ = clock.timed(
            lambda: prog.lower(*operands).compile().memory_analysis())
        need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
        if not limit or need <= limit:
            return lines, {"program_bytes": int(need),
                           "bytes_limit": limit, "cuts": cuts,
                           "compile_s": compile_s}
        cuts.append(f"L={lines} needs {need} B > {limit} B on the chip")
        lines //= 2


def stream_phase(name: str, lines: int, clock, dev, ref=None,
                 **engine_kw) -> "tuple[dict, object]":
    from repro.traffic import (EngineConfig, run_stream, stream_program,
                               validate_run)
    ecfg = EngineConfig(remotes=REMOTES, lines=lines, block=BLOCK,
                        **engine_kw)
    eng, scfg = ecfg.build(), stream_cfg()
    run, compile_s, wall_s = clock.timed(lambda: run_stream(eng, scfg))
    # the final state is 5 GB of device memory the next phase needs.
    run = run._replace(state=None)
    rec = {"phase": name, "device_kind": dev.device_kind,
           "remotes": REMOTES, "lines": lines, "block": BLOCK, "ops": OPS,
           "kernel_backend": eng.kernel_backend, "packed": eng.packed,
           "compile_s": compile_s, "wall_s": wall_s, **summary(run)}
    check(run.completed, f"{name}: stream did not drain")
    if ref is None:
        validate_run(run)
        rec["validated"] = "MultiNodeRef"
    else:
        assert_identical(ref, run, name)
        rec["bit_identical_to"] = "stream_xla"
    if eng.kernel_backend == "pallas":
        # the kernels are really in the program the chip ran.
        prog, operands = stream_program(eng, scfg)
        text, c_s, _ = clock.timed(
            lambda: prog.lower(*operands).compile().as_text())
        check("tpu_custom_call" in text, f"{name}: no Pallas kernel")
        rec["tpu_custom_call"] = True
        rec["text_compile_s"] = c_s
    rec["peak_bytes_in_use"] = peak_bytes(dev)
    return rec, run


def one_chip(devs, clock) -> None:
    dev = devs[0]
    lines, fit = fit_lines(dev, clock)
    print(json.dumps({"phase": "fit", "device_kind": dev.device_kind,
                      "lines": lines, **fit}), flush=True)
    rec, ref = stream_phase("stream_xla", lines, clock, dev)
    print(json.dumps(rec), flush=True)
    rec, _ = stream_phase("stream_pallas", lines, clock, dev, ref=ref,
                          kernel_backend="pallas")
    print(json.dumps(rec), flush=True)
    rec, _ = stream_phase("stream_packed", lines, clock, dev, ref=ref,
                          kernel_backend="pallas", packed=True)
    print(json.dumps(rec), flush=True)


def four_chips(devs, clock) -> None:
    import jax
    from repro.traffic import (EngineConfig, FleetConfig, fleet_steps,
                               run_fleet, run_stream)
    devs = devs[:4]
    members = tuple(
        (EngineConfig(remotes=r, lines=LINES, block=BLOCK),
         stream_cfg(trace=False)) for r in FLEET_REMOTES)
    fleet = FleetConfig(members=members, mesh_devices=len(devs))
    steps = fleet_steps(fleet)
    runs, compile_s, wall_s = clock.timed(lambda: run_fleet(fleet))
    runs = [r._replace(state=None) for r in runs]
    peaks = [peak_bytes(d) for d in devs]
    # one member per chip: chip 0 holds no more than any other chip
    # (checked where the backend reports memory, as a TPU does).
    check(min(peaks) < 0 or peaks[0] <= 1.25 * max(peaks[1:]),
          f"fleet members stacked on chip 0: peak bytes {peaks}")
    print(json.dumps({
        "phase": "fleet_sharded", "device_kind": devs[0].device_kind,
        "mesh_devices": len(devs), "lines": LINES, "block": BLOCK,
        "ops": OPS, "members_remotes": list(FLEET_REMOTES),
        "fleet_steps": steps, "compile_s": compile_s, "wall_s": wall_s,
        "completed": [r.completed for r in runs],
        "msg_total": [int(r.msg_count.sum()) for r in runs],
        "peak_bytes_in_use": peaks}), flush=True)
    check(all(r.completed for r in runs), "fleet member did not drain")

    def solo(i):
        # each member's solo run on its own chip, at the fleet's budget.
        with jax.default_device(devs[i]):
            eng = members[i][0].build()
            return run_stream(eng, stream_cfg(steps=steps, trace=False)
                              )._replace(state=None)

    with ThreadPoolExecutor(len(devs)) as pool:
        solos, compile_s, wall_s = clock.timed(
            lambda: list(pool.map(solo, range(len(devs)))))
    for r, run, s in zip(FLEET_REMOTES, runs, solos):
        assert_identical(s, run, f"fleet member R={r}")
    print(json.dumps({
        "phase": "fleet_solo", "device_kind": devs[0].device_kind,
        "lines": LINES, "fleet_steps": steps,
        "compile_s": compile_s, "wall_s": wall_s,
        "members": [{"remotes": r, **summary(s)}
                    for r, s in zip(FLEET_REMOTES, solos)],
        "bit_identical_to": "fleet_sharded",
        "peak_bytes_in_use": [peak_bytes(d) for d in devs]}), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded fleet phase and the "
                         "solo runs it is compared with")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    devs = require_tpu(args.chips)
    enable_compile_cache()
    clock = CompileClock()
    if args.chips == 4:
        four_chips(devs, clock)
    else:
        one_chip(devs, clock)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
