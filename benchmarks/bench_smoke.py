"""CI benchmark smoke gate: tiny fan-out + streaming runs, machine-readable.

    PYTHONPATH=src python -m benchmarks.bench_smoke \
        --out BENCH_smoke.json --baseline benchmarks/BENCH_baseline.json

Unlike ``benchmarks/run.py`` (which prints the paper-figure CSV), this
writes a JSON record built from the SIMULATION's own deterministic
metrics — sustained ops/step, invalidations per exclusive grant, max
request wait, all measured in engine steps — so the gate is stable across
runner hardware: only a semantic regression (scheduling, arbitration,
fan-out, backpressure) moves the numbers.  Wall-clock and compile times
ride along as informational fields and are never gated.

Gate rules (exit 1 on violation):

* every streaming run must COMPLETE within its step budget;
* fan-out exactness: engine invalidations/store == oracle == R-1;
* ops/step must not regress more than ``--tolerance`` (default 30%)
  against the committed baseline, per configuration;
* protocol-subset efficiency: interconnect messages per retired op
  (full_moesi / enhanced_mesi / read_only on the same zipfian stream)
  must not inflate more than ``--tolerance`` vs baseline;
* fleet exactness: the vmapped R x W grid and the H in {1,2,4} homes
  sweep each run as ONE jitted program, and every member's counters
  and message counts must be BIT-identical to a solo ``run_stream``
  at the fleet's shared step budget (the per-point vs fleet compile
  times ride along un-gated as the amortization record);
* observability: the traced acceptance stream (R=64, H in {1,2}) must
  stay semantically bit-identical to the untraced one, check clean
  against the online protocol specs, and cost at most
  ``OBS_OVERHEAD_LIMIT`` (1.15x) wall time — observability-overhead
  regressions gate like perf regressions;
* open-loop knee (docs/serving.md): the R=8 Poisson sweep's
  sub-saturation points must complete with p99 sojourn within
  ``--tolerance`` of baseline, the past-saturation point must show
  unserved backlog (overload detected), and the middle point's
  retirement trace must replay EXACTLY against ``MultiNodeRef`` —
  admission gates when ops issue, never what they do.

``--write-baseline`` refreshes the committed baseline file instead of
comparing (run it locally when a PR intentionally shifts throughput).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

#: (workload, n_remotes, n_lines, ops, width, homes) per streaming smoke
#: config — small enough for a CI job, wide enough (R=8, R=32) to exercise
#: the past-4-remotes flat layout, one W=2 config covering the multi-op
#: issue window, one NON-zipfian traffic shape (producer_consumer: steady-
#: state dirty forwarding) so the gate covers more than hot-line skew, and
#: one H=2 config keeping the multi-home [H, R, L/H] engine on the gate.
STREAM_CONFIGS = (("zipfian", 2, 16, 32, 1, 1), ("zipfian", 8, 16, 32, 1, 1),
                  ("zipfian", 32, 16, 32, 1, 1), ("zipfian", 8, 16, 32, 2, 1),
                  ("producer_consumer", 8, 16, 32, 1, 1),
                  ("migratory", 8, 16, 32, 1, 1),
                  ("false_sharing", 8, 16, 32, 1, 1),
                  ("zipfian", 8, 16, 32, 1, 2))
FANOUT_REMOTES = (2, 8)

#: protocol-subset message-efficiency gate: the SAME zipfian stream
#: through each compiled protocol subset, gated on interconnect
#: messages per retired op (the figure-of-merit customizing the stack
#: is supposed to move).  ``read_only`` only admits loads, so its
#: variant pins ``store_frac=0``.
SUBSET_CONFIG = dict(n_remotes=8, n_lines=16, ops=32)
SUBSET_VARIANTS = (("full_moesi", None), ("enhanced_mesi", None),
                   ("read_only", {"store_frac": 0.0}))

#: vmapped fleet sweep: the R x W grid batched into ONE jitted program
#: (``repro.traffic.fleet``), every member gated BIT-identical to its
#: solo ``run_stream`` at the fleet's shared step budget, plus the
#: H in {1,2,4} homes sweep riding the flat-layout emulation.  The
#: per-point vs fleet compile times are recorded (never gated — compile
#: time is wall clock) as the amortization evidence for docs/perf.md.
FLEET_CONFIG = dict(n_lines=16, ops=32)
FLEET_GRID = tuple((r, w) for r in (4, 8, 16, 32) for w in (1, 2, 4))
FLEET_HOMES = (1, 2, 4)
FLEET_HOMES_REMOTES = 8
FLEET_HOME_BW = 1

#: observability-overhead harness: the acceptance config (zipfian R=64)
#: at H in {1, 2}, traced (EWF ring + online NFA specs + phase
#: attribution) vs untraced, best-of-N each.  The ratio is GATED at
#: OBS_OVERHEAD_LIMIT — observability-overhead regressions fail CI like
#: any perf regression — and the traced run must stay semantically
#: bit-identical (same ops retired, same message counts) with zero spec
#: violations.
OBS_CONFIG = dict(n_remotes=64, n_lines=32, block=4, ops=24)
OBS_HOMES = (1, 2)
OBS_OVERHEAD_LIMIT = 1.15

#: open-loop knee curve (docs/serving.md): seeded Poisson arrivals at
#: three offered loads (ops/step/remote) through the FIFO + reserve
#: admission loop.  Closed-loop capacity at this config is ~0.084
#: ops/step/remote (the committed r8 streaming baseline / 8), so 0.02 and
#: 0.05 sit below the knee and 0.30 is past saturation — the overload
#: point runs a FIXED window (the arrival span) and must end with
#: unserved backlog; the sub-saturation points must complete, with p99
#: sojourn gated at ±tolerance against the committed baseline.  The
#: middle point replays its retirement trace against MultiNodeRef —
#: oracle exactness UNDER the admission loop, on the gate.
KNEE_CONFIG = dict(workload="zipfian", n_remotes=8, n_lines=16, ops=48)
KNEE_RATES = (0.02, 0.05, 0.30)
KNEE_OVERLOAD_FROM = 0.20          # rates >= this expect overload
KNEE_VALIDATE_RATE = 0.05          # this point oracle-validates
KNEE_ADMISSION = (16, 2)           # (max_inflight, reserve watermark)


def run_fanout() -> dict:
    """Tiny fan-out exactness check: engine count == oracle == R-1."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core import CoherentStore, FULL_MOESI, MultiNodeRef

    out = {}
    n_lines, block = 8, 2
    for n_remotes in FANOUT_REMOTES:
        cs = CoherentStore(jnp.zeros((n_lines, block), jnp.float32),
                           FULL_MOESI, n_remotes=n_remotes, max_rounds=128)
        ids = np.arange(n_lines)
        for node in range(n_remotes):
            cs.read(ids, node=node)
        before = cs.interconnect_messages.get("HOME_DOWNGRADE_I", 0)
        cs.write(ids, jnp.ones((n_lines, block), jnp.float32), node=0)
        sent = cs.interconnect_messages.get("HOME_DOWNGRADE_I", 0) - before
        ref = MultiNodeRef(1, n_remotes=n_remotes)
        for node in range(n_remotes):
            ref.load(node, 0)
        rbefore = ref.invalidation_messages()
        ref.store(0, 0, 1)
        out[f"r{n_remotes}"] = {
            "invals_per_store": sent / n_lines,
            "oracle_invals_per_store": ref.invalidation_messages() - rbefore,
            "model": n_remotes - 1,
        }
    return out


def run_streaming() -> dict:
    """Tiny zipfian streaming runs; deterministic throughput metrics."""
    from repro.traffic import (EngineConfig, StreamConfig, WorkloadSpec,
                               default_steps, run_stream, summarize)

    out = {}
    for workload, n_remotes, n_lines, ops, width, homes in STREAM_CONFIGS:
        ecfg = EngineConfig(remotes=n_remotes, lines=n_lines, homes=homes)
        steps = default_steps(ops, n_remotes)
        scfg = StreamConfig(workload=WorkloadSpec(workload, ops=ops,
                                                  seed=0),
                            steps=steps, width=width)
        t0 = time.perf_counter()
        run = run_stream(ecfg.build(), scfg)              # compile + run
        t_compile = time.perf_counter() - t0
        t0 = time.perf_counter()
        run = run_stream(ecfg.build(), scfg)
        wall = time.perf_counter() - t0
        s = summarize(run.counters, run.msg_count)
        # zipfian keys keep their historical names so the committed
        # baseline and the cross-PR trajectory stay comparable.
        key = f"r{n_remotes}" if width == 1 else f"r{n_remotes}_w{width}"
        if homes > 1:
            key = f"{key}_h{homes}"
        if workload != "zipfian":
            key = f"{workload}_{key}"
        out[key] = {
            "completed": bool(run.completed),
            "ops_per_step": round(float(s["ops_per_step"]), 6),
            "inval_per_excl_grant": round(
                float(s["inval_per_excl_grant"]), 6),
            "max_wait": int(max(s["max_wait"])),
            "mean_mshr_occupancy": round(
                float(s["mean_mshr_occupancy"]), 3),
            "ops_retired": int(s["ops_retired"]),
            "steps": steps,
            # informational only — never gated:
            "wall_s": round(wall, 3),
            "compile_s": round(t_compile, 3),
        }
    return out


def run_subsets() -> dict:
    """Messages per retired op across protocol subsets.

    Deterministic (seeded workload, seeded engine), so the ratio gates
    against the committed baseline like ops/step does: a protocol-table
    change that inflates interconnect traffic for the same work fails
    CI even when throughput holds."""
    import numpy as np
    from repro.traffic import (EngineConfig, StreamConfig, WorkloadSpec,
                               default_steps, run_stream, summarize)

    cfg = SUBSET_CONFIG
    steps = default_steps(cfg["ops"], cfg["n_remotes"])
    out = {}
    for subset, params in SUBSET_VARIANTS:
        wspec = WorkloadSpec("zipfian", ops=cfg["ops"], seed=0,
                             params=params or ())
        ecfg = EngineConfig(remotes=cfg["n_remotes"],
                            lines=cfg["n_lines"], subset=subset)
        run = run_stream(ecfg.build(), StreamConfig(workload=wspec,
                                                    steps=steps))
        s = summarize(run.counters, run.msg_count)
        msgs = int(np.asarray(run.msg_count).sum())
        out[subset] = {
            "completed": bool(run.completed),
            "msgs_per_op": round(msgs / max(int(s["ops_retired"]), 1), 6),
            "ops_per_step": round(float(s["ops_per_step"]), 6),
            "ops_retired": int(s["ops_retired"]),
        }
    return out


def _bit_identical(fleet_run, solo_run) -> bool:
    """Counters + message counts exactly equal — the fleet contract."""
    import numpy as np
    if bool(fleet_run.completed) != bool(solo_run.completed):
        return False
    if not np.array_equal(np.asarray(fleet_run.msg_count),
                          np.asarray(solo_run.msg_count)):
        return False
    for a, b in zip(fleet_run.counters, solo_run.counters):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            return False
    return True


def run_fleet_bench() -> dict:
    """The vmapped fleet sweep vs per-point solo runs.

    Two fleets run, each as ONE jitted program: the zipfian R x W grid
    and the H in {1,2,4} homes sweep.  Every member is then re-run SOLO
    (fresh engine, same shared step budget) and the gate demands the
    fleet member's counters and message counts equal the solo run's
    bit-for-bit — batching must be a pure execution strategy, never a
    semantic one.  The solo first-call-minus-warm-call compile times sum
    to the per-point compile cost the fleet amortizes; the ratio is
    recorded for the trajectory but never gated (compile time is wall
    clock)."""
    from repro.traffic import (EngineConfig, FleetConfig, StreamConfig,
                               WorkloadSpec, fleet_steps, run_fleet,
                               run_stream, summarize)

    cfg = FLEET_CONFIG

    def _timed_fleet(fleet):
        t0 = time.perf_counter()
        runs = run_fleet(fleet)                       # compile + run
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        runs = run_fleet(fleet)
        warm = time.perf_counter() - t0
        return runs, max(cold - warm, 0.0), warm

    def _timed_solo(ecfg, scfg):
        run = run_stream(ecfg.build(), scfg)          # compile + warm
        t0 = time.perf_counter()
        run = run_stream(ecfg.build(), scfg)
        warm = time.perf_counter() - t0
        return run, warm

    def _solo_compile(ecfg, scfg):
        t0 = time.perf_counter()
        run_stream(ecfg.build(), scfg)
        return time.perf_counter() - t0

    # --- R x W grid, one program -----------------------------------
    members = tuple(
        (EngineConfig(remotes=r, lines=cfg["n_lines"]),
         StreamConfig(workload=WorkloadSpec("zipfian", ops=cfg["ops"],
                                            seed=0), width=w))
        for r, w in FLEET_GRID)
    fleet = FleetConfig(members=members)
    steps = fleet_steps(fleet)
    fruns, fleet_compile, fleet_warm = _timed_fleet(fleet)

    grid = {}
    solo_compile_total = 0.0
    for (ecfg, scfg), (r, w), frun in zip(members, FLEET_GRID, fruns):
        solo_cfg = StreamConfig(workload=scfg.workload, width=w,
                                steps=steps)
        cold = _solo_compile(ecfg, solo_cfg)
        solo, warm = _timed_solo(ecfg, solo_cfg)
        point_compile = max(cold - warm, 0.0)
        solo_compile_total += point_compile
        s = summarize(frun.counters, frun.msg_count)
        grid[f"r{r}_w{w}"] = {
            "completed": bool(frun.completed),
            "bit_identical_to_solo": _bit_identical(frun, solo),
            "ops_per_step": round(float(s["ops_per_step"]), 6),
            "max_wait": int(max(s["max_wait"])),
            "ops_retired": int(s["ops_retired"]),
            # informational only — never gated:
            "compile_s": round(point_compile, 3),
            "wall_s": round(warm, 3),
        }

    # --- homes sweep H in {1,2,4}, one program ---------------------
    hmembers = tuple(
        (EngineConfig(remotes=FLEET_HOMES_REMOTES, lines=cfg["n_lines"],
                      homes=h, home_bw=FLEET_HOME_BW),
         StreamConfig(workload=WorkloadSpec("zipfian", ops=cfg["ops"],
                                            seed=0)))
        for h in FLEET_HOMES)
    hfleet = FleetConfig(members=hmembers)
    hsteps = fleet_steps(hfleet)
    hruns, homes_compile, _ = _timed_fleet(hfleet)

    homes = {}
    for (ecfg, scfg), h, frun in zip(hmembers, FLEET_HOMES, hruns):
        solo_cfg = StreamConfig(workload=scfg.workload, steps=hsteps)
        solo, warm = _timed_solo(ecfg, solo_cfg)
        s = summarize(frun.counters, frun.msg_count)
        homes[f"h{h}"] = {
            "completed": bool(frun.completed),
            "bit_identical_to_solo": _bit_identical(frun, solo),
            "ops_per_step": round(float(s["ops_per_step"]), 6),
            "max_wait": int(max(s["max_wait"])),
            "ops_retired": int(s["ops_retired"]),
        }

    return {
        "grid": grid,
        "homes": homes,
        # informational only — never gated (compile time is wall clock):
        "compile": {
            "points": len(FLEET_GRID),
            "steps": steps,
            "per_point_total_s": round(solo_compile_total, 3),
            "fleet_s": round(fleet_compile, 3),
            "homes_fleet_s": round(homes_compile, 3),
            "fleet_wall_s": round(fleet_warm, 3),
            "amortization_x": round(
                solo_compile_total / max(fleet_compile, 1e-9), 2),
        },
    }


def run_observability(repeats: int = 5) -> dict:
    """Traced-vs-untraced overhead on the acceptance stream (R=64).

    Both variants run the SAME workload through fresh engines; the traced
    program folds the full observability plane (EWF ring capture, online
    req_resp + single_writer NFA checking, phase attribution) through the
    scan.  Reports the best of the per-pair wall ratios over ``repeats``
    back-to-back (untraced, traced) pairs — gated at
    ``OBS_OVERHEAD_LIMIT`` — plus the semantic-identity and
    zero-violations facts the gate also enforces."""
    import numpy as np
    from repro.traffic import (EngineConfig, ObserveConfig, StreamConfig,
                               WorkloadSpec, default_steps, run_stream,
                               summarize)

    cfg = OBS_CONFIG
    n_remotes, n_lines = cfg["n_remotes"], cfg["n_lines"]
    wspec = WorkloadSpec("zipfian", ops=cfg["ops"], seed=0)
    steps = default_steps(cfg["ops"], n_remotes)
    obs_cfg = ObserveConfig(capture=True, capacity=1 << 12,
                            specs=("req_resp", "single_writer"),
                            attribution=True)
    out = {}
    for homes in OBS_HOMES:
        variants = (("untraced", None), ("traced", obs_cfg))
        ecfg = EngineConfig(remotes=n_remotes, lines=n_lines,
                            block=cfg["block"], homes=homes)

        def _measure(observe):
            t0 = time.perf_counter()
            run = run_stream(ecfg.build(), StreamConfig(
                workload=wspec, steps=steps, observe=observe))
            return run, time.perf_counter() - t0

        runs = {}
        for tag, observe in variants:               # compile + warm
            runs[tag] = [_measure(observe)[0], float("inf")]
        # interleave the timed repeats: an A-block-then-B-block layout
        # lets machine-load drift between the blocks masquerade as
        # observability overhead (or hide it).  Each back-to-back
        # (untraced, traced) pair shares its drift, so the per-pair
        # ratio is drift-free; best-of over pairs then strips the
        # noise-hit pairs, matching the best-of wall convention the
        # other bench_* metrics use.
        ratios = []
        for _ in range(repeats):
            pair = {}
            for tag, observe in variants:
                run, dt = _measure(observe)
                pair[tag] = dt
                runs[tag] = [run, min(runs[tag][1], dt)]
            ratios.append(pair["traced"] / pair["untraced"])
        ratio = float(min(ratios))
        untraced, u_best = runs["untraced"]
        traced, t_best = runs["traced"]
        s = summarize(traced.counters, traced.msg_count)
        identical = (
            bool(untraced.completed) and bool(traced.completed)
            and np.array_equal(np.asarray(untraced.msg_count),
                               np.asarray(traced.msg_count))
            and int(np.asarray(untraced.counters.retired).sum())
            == int(np.asarray(traced.counters.retired).sum()))
        out[f"r{n_remotes}_h{homes}"] = {
            "config": dict(cfg, homes=homes, steps=steps),
            "completed": bool(traced.completed),
            "identical_semantics": identical,
            "violations": len(traced.obs.violations),
            "captured_words": int(len(traced.obs.words)),
            "overhead_ratio": round(ratio, 4),
            "overhead_limit": OBS_OVERHEAD_LIMIT,
            "untraced_steps_per_s": round(steps / u_best, 1),
            "traced_steps_per_s": round(steps / t_best, 1),
            "ops_per_step": round(float(s["ops_per_step"]), 4),
            "phase_p99": {ph: p["p99"] for ph, p in
                          traced.obs.phase_percentiles().items()},
        }
    return out


def run_knee() -> dict:
    """Open-loop knee curve: p50/p99/p999 sojourn vs offered load.

    Deterministic end to end (seeded arrivals, seeded workload,
    deterministic engine), so the sub-saturation p99s gate against the
    committed baseline like ops/step does.  The overload point measures a
    FIXED window — exactly the arrival span — so the queue is still
    growing when the window closes: ``backlog > 0`` is the structural
    overload signature the gate demands (an auto budget would let the
    finite stream drain and hide the collapse)."""
    import numpy as np
    from repro.traffic import (AdmissionConfig, ArrivalSpec, EngineConfig,
                               StreamConfig, WorkloadSpec, run_stream,
                               sojourn_summary, validate_run)

    cfg = KNEE_CONFIG
    ecfg = EngineConfig(remotes=cfg["n_remotes"], lines=cfg["n_lines"])
    out = {}
    for rate in KNEE_RATES:
        arr = ArrivalSpec("poisson", rate=rate, seed=1)
        sched = arr.materialize(cfg["ops"], cfg["n_remotes"])
        last_arrival = int(np.asarray(sched.step).max())
        expect_overload = rate >= KNEE_OVERLOAD_FROM
        validate = rate == KNEE_VALIDATE_RATE
        scfg = StreamConfig(
            workload=WorkloadSpec(cfg["workload"], ops=cfg["ops"], seed=0),
            arrivals=arr,
            admission=AdmissionConfig(*KNEE_ADMISSION),
            steps=last_arrival if expect_overload else 0,
            collect_trace=validate)
        t0 = time.perf_counter()
        run = run_stream(ecfg.build(), scfg)
        wall = time.perf_counter() - t0
        if validate:
            validate_run(run)   # oracle EXACT under the admission loop
        s = sojourn_summary(run)
        perc = s["sojourn_percentiles"]
        out[f"rate{rate:g}"] = {
            "offered_per_remote": rate,
            "expect_overload": expect_overload,
            "completed": bool(run.completed),
            "backlog": int(s["backlog"]),
            "sojourn_p50": perc["p50"],
            "sojourn_p99": perc["p99"],
            "sojourn_p999": perc["p999"],
            "admit_wait_p99": s["admit_wait_percentiles"]["p99"],
            "validated": bool(validate),
            "steps": int(run.counters.steps),
            "last_arrival": last_arrival,
            # informational only — never gated:
            "wall_s": round(wall, 3),
        }
    return out


def collect() -> dict:
    import jax
    rec = {
        "schema": 4,
        "jax_version": jax.__version__,
        "generated_unix": int(time.time()),
        "fanout": run_fanout(),
        "streaming": run_streaming(),
        "subsets": run_subsets(),
        "fleet": run_fleet_bench(),
        "observability": run_observability(),
        "knee": run_knee(),
    }
    return rec


def gate(current: dict, baseline: dict, tolerance: float) -> list:
    """Return the list of violation strings (empty = pass)."""
    bad = []
    for key, rec in current["fanout"].items():
        if not (rec["invals_per_store"] == rec["oracle_invals_per_store"]
                == rec["model"]):
            bad.append(f"fanout {key}: engine {rec['invals_per_store']} != "
                       f"oracle {rec['oracle_invals_per_store']} != model "
                       f"{rec['model']}")
    for key, rec in current["streaming"].items():
        if not rec["completed"]:
            bad.append(f"streaming {key}: did not complete within "
                       f"{rec['steps']} steps")
        base = baseline.get("streaming", {}).get(key) if baseline else None
        if base is None:
            continue
        floor = (1.0 - tolerance) * base["ops_per_step"]
        if rec["ops_per_step"] < floor:
            bad.append(
                f"streaming {key}: ops/step {rec['ops_per_step']:.4f} "
                f"regressed >{tolerance:.0%} vs baseline "
                f"{base['ops_per_step']:.4f} (floor {floor:.4f})")
    # subset gate: every subset completes, and messages per retired op
    # must not INFLATE more than tolerance vs baseline — a protocol-
    # table change that buys nothing but extra interconnect traffic
    # fails even when ops/step holds.
    for key, rec in current.get("subsets", {}).items():
        if not rec["completed"]:
            bad.append(f"subsets {key}: stream did not complete")
        base = baseline.get("subsets", {}).get(key) if baseline else None
        if base is None:
            continue
        ceil = (1.0 + tolerance) * base["msgs_per_op"]
        if rec["msgs_per_op"] > ceil:
            bad.append(
                f"subsets {key}: msgs/op {rec['msgs_per_op']:.4f} "
                f"inflated >{tolerance:.0%} vs baseline "
                f"{base['msgs_per_op']:.4f} (ceiling {ceil:.4f})")
    # fleet gate: batching is an execution strategy, never a semantic
    # one — every member must complete AND be bit-identical to its solo
    # run; ops/step gates against baseline like streaming.  The compile
    # amortization numbers are recorded but NOT gated (wall clock).
    fl = current.get("fleet", {})
    for section in ("grid", "homes"):
        for key, rec in fl.get(section, {}).items():
            tag = f"fleet {section} {key}"
            if not rec["completed"]:
                bad.append(f"{tag}: did not complete")
            if not rec["bit_identical_to_solo"]:
                bad.append(f"{tag}: fleet member diverged from its solo "
                           f"run (counters / message counts not "
                           f"bit-identical)")
            base = (baseline.get("fleet", {}).get(section, {}).get(key)
                    if baseline else None)
            if base is None:
                continue
            floor = (1.0 - tolerance) * base["ops_per_step"]
            if rec["ops_per_step"] < floor:
                bad.append(
                    f"{tag}: ops/step {rec['ops_per_step']:.4f} "
                    f"regressed >{tolerance:.0%} vs baseline "
                    f"{base['ops_per_step']:.4f} (floor {floor:.4f})")
    # observability gate: absolute rules, no baseline needed — the traced
    # program must not perturb semantics, must check clean, and must stay
    # within the committed overhead budget.
    for key, rec in current.get("observability", {}).items():
        if not rec["completed"]:
            bad.append(f"observability {key}: traced stream did not "
                       f"complete")
        if not rec["identical_semantics"]:
            bad.append(f"observability {key}: traced run diverged from "
                       f"untraced (ops retired / message counts)")
        if rec["violations"]:
            bad.append(f"observability {key}: {rec['violations']} online "
                       f"protocol-spec violation(s) on a clean stream")
        if rec["overhead_ratio"] > rec["overhead_limit"]:
            bad.append(
                f"observability {key}: overhead ratio "
                f"{rec['overhead_ratio']:.3f} exceeds "
                f"{rec['overhead_limit']:.2f} (traced "
                f"{rec['traced_steps_per_s']:.0f} vs untraced "
                f"{rec['untraced_steps_per_s']:.0f} steps/s)")
    # knee gate: the open-loop service model must keep its shape — the
    # past-saturation point detects overload (unserved backlog in a
    # fixed window), the sub-saturation points complete with p99 sojourn
    # within tolerance of the committed baseline.
    for key, rec in current.get("knee", {}).items():
        if rec["expect_overload"]:
            if rec["backlog"] <= 0:
                bad.append(
                    f"knee {key}: offered {rec['offered_per_remote']} "
                    f"past saturation but no unserved backlog — overload "
                    f"not detected")
            continue
        if not rec["completed"]:
            bad.append(f"knee {key}: sub-saturation point did not drain")
        base = baseline.get("knee", {}).get(key) if baseline else None
        if base is None:
            continue
        ceil = (1.0 + tolerance) * base["sojourn_p99"]
        if rec["sojourn_p99"] > ceil:
            bad.append(
                f"knee {key}: p99 sojourn {rec['sojourn_p99']:.0f} "
                f"regressed >{tolerance:.0%} vs baseline "
                f"{base['sojourn_p99']:.0f} (ceiling {ceil:.0f})")
    return bad


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="BENCH_smoke.json",
                    help="where to write the machine-readable record")
    ap.add_argument("--baseline",
                    default=os.path.join(os.path.dirname(__file__),
                                         "BENCH_baseline.json"),
                    help="committed baseline JSON to gate against")
    ap.add_argument("--tolerance", type=float, default=0.30,
                    help="max allowed ops/step regression (fraction)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="refresh the baseline file instead of gating")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    current = collect()
    with open(args.out, "w") as f:
        json.dump(current, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {args.out}")

    if args.write_baseline:
        # the committed baseline carries ONLY deterministic metrics —
        # the observability overhead ratio is a wall-clock ratio gated by
        # an absolute limit instead, and moves with the machine that
        # happened to refresh it.
        base = {k: v for k, v in current.items() if k != "observability"}
        with open(args.baseline, "w") as f:
            json.dump(base, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"refreshed baseline {args.baseline}")
        return

    baseline = None
    if os.path.exists(args.baseline):
        with open(args.baseline) as f:
            baseline = json.load(f)
    else:
        print(f"warning: no baseline at {args.baseline}; "
              "gating exactness/completion only")

    violations = gate(current, baseline, args.tolerance)
    for key, rec in sorted(current["streaming"].items()):
        base = (baseline or {}).get("streaming", {}).get(key, {})
        print(f"streaming {key}: ops/step {rec['ops_per_step']:.4f} "
              f"(baseline {base.get('ops_per_step', float('nan')):.4f}) "
              f"max_wait {rec['max_wait']} wall {rec['wall_s']}s "
              f"compile {rec['compile_s']}s")
    for key, rec in sorted(current.get("subsets", {}).items()):
        print(f"subsets {key}: msgs/op {rec['msgs_per_op']:.4f} "
              f"ops/step {rec['ops_per_step']:.4f}")
    fl = current.get("fleet", {})
    for section in ("grid", "homes"):
        for key, rec in sorted(fl.get(section, {}).items()):
            print(f"fleet {section} {key}: ops/step "
                  f"{rec['ops_per_step']:.4f} bit_identical "
                  f"{rec['bit_identical_to_solo']}")
    if fl:
        c = fl["compile"]
        print(f"fleet compile: {c['points']} points, per-point total "
              f"{c['per_point_total_s']}s vs fleet {c['fleet_s']}s "
              f"({c['amortization_x']}x amortization; homes fleet "
              f"{c['homes_fleet_s']}s)")
    for key, rec in sorted(current.get("observability", {}).items()):
        print(f"observability {key}: overhead "
              f"{rec['overhead_ratio']:.3f}x (limit "
              f"{rec['overhead_limit']:.2f}) violations "
              f"{rec['violations']} identical "
              f"{rec['identical_semantics']}")
    for key, rec in sorted(current.get("knee", {}).items(),
                           key=lambda kv: kv[1]["offered_per_remote"]):
        print(f"knee {key}: p50/p99/p999 sojourn "
              f"{rec['sojourn_p50']:.0f}/{rec['sojourn_p99']:.0f}/"
              f"{rec['sojourn_p999']:.0f} backlog {rec['backlog']}"
              + (" OVERLOAD" if rec["expect_overload"] else "")
              + (" validated" if rec["validated"] else ""))
    if violations:
        for v in violations:
            print("FAIL:", v)
        raise SystemExit(1)
    print("bench-smoke: PASS")


if __name__ == "__main__":
    main()
