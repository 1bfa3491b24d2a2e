"""The benchmark harness: one cell, one seed, one measured window.

A cell of ``BENCHMARK.json`` names a configuration
(``configs/<name>.json``: the engine, its dtype and its plain reference,
``references/<name>.py``) and a traffic mix (``traffic/<name>.json``,
read by ``traffic_gen``, which finds its access pattern as
``patterns/<kind>.py``).  Its per-layer metrics are readers of their own
(``metrics/<name>.py``).  The harness finds every one of them by name, so
a cell, a configuration, a traffic mix or a metric is added by adding
files and entries.

The unit of work is a sweep point: one ``run_stream`` call from a fresh
``engine.init()`` with the automatic step budget, on inputs generated
from ``(seed, point)``.  Set-up builds the engine and runs one warm-up
point, which compiles the stream program and the benchmark's read-back
program; then points run back to back for about ``seconds``.
After the window, every point is compared with the plain reference
(``checks``).

A configuration file that holds a ``"fleet"`` group beside ``"engine"``
(``{"remotes": [...]}``) is a sweep run as one program: a point is then
one ``run_fleet`` call whose members are the engine at each of
``fleet.remotes`` (``engine.remotes`` is the largest), one member per
chip of the cell's ``chips``, or all on one chip where it asks for one
(``FleetTarget``).  Each member draws
its inputs from a generator of its own, its final state is read back on
the chip that holds it, and it is compared with the reference as a
one-engine point is; the point is correct when every member is.  The
metrics read a fleet point as one call: its ops are all members' ops,
its steps the one budget every member scans.
"""
from __future__ import annotations

import functools
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, NamedTuple, Optional

import numpy as np

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent.parent
BENCHMARK = CHECKOUT / "BENCHMARK.json"
#: JAX's persistent compilation cache: a fixed path inside the checkout.
CACHE_DIR = CHECKOUT / ".jax_cache"

sys.path.insert(0, str(HERE))
if str(CHECKOUT / "src") not in sys.path:
    sys.path.insert(0, str(CHECKOUT / "src"))
import byname  # noqa: E402
import checks  # noqa: E402
import trace_reduce  # noqa: E402
import traffic_gen  # noqa: E402

NAME = byname.NAME
load_module = byname.load_module
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class Cell(NamedTuple):
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench: Optional[dict] = None) -> Cell:
    """A cell of ``BENCHMARK.json``, with its configuration and traffic
    files read and its metrics narrowed to those it reports."""
    bench = _read_json(BENCHMARK) if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read_json(CHECKOUT / configs[w["config"]]["file"])
    traffic = _read_json(byname.path("traffic", w["traffic"], ".json"))
    return Cell(name, int(w["chips"]), config, traffic,
                [m for m in bench["end_to_end"] if applies(m, name)],
                [m for m in bench["per_layer"] if applies(m, name)])


class CompileClock:
    """JAX's compile-duration events as host-clock intervals (copied from
    the program's ``chip_smoke.py``)."""

    def __init__(self):
        import jax
        self.spans: List[tuple] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name in COMPILE_EVENTS:
            end = time.perf_counter()
            self.spans.append((end - secs, end))

    def count(self, lo: float, hi: float) -> int:
        return sum(1 for s, e in self.spans if e > lo and s < hi)


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache: the directory that
    ``JAX_COMPILATION_CACHE_DIR`` names where it is set, else
    ``CACHE_DIR``."""
    import os

    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def check_interface() -> None:
    """The op and message codes the generator and the reference use are
    still the program's."""
    from repro.core.messages import MsgType
    from repro.core.protocol import LocalOp
    ref = load_module("references", "moesi_directory")
    ok = ((LocalOp.NOP, LocalOp.LOAD, LocalOp.STORE)
          == (traffic_gen.NOP, traffic_gen.LOAD, traffic_gen.STORE)
          and [MsgType(i).name for i in range(16)] == list(ref.MESSAGES))
    if not ok:
        raise RuntimeError("the program's op or message codes changed")


def _extractor():
    """The benchmark's read-back program: the touched lines of the final
    state, and what lies off them.  Where ``real`` (the member's remote
    count) is given, the state is a fleet member's, padded to the fleet's
    largest member, and every non-zero entry of a padded remote's row
    counts as a stray one."""
    import jax
    import jax.numpy as jnp

    def stray(a, line_axis, off):
        other = tuple(i for i in range(a.ndim) if i != line_axis)
        per_line = (a != 0).sum(other) if other else (a != 0)
        return jnp.where(off, per_line, 0).sum()

    def off_max(a, line_axis, off):
        shape = [1] * a.ndim
        shape[line_axis] = -1
        return jnp.where(off.reshape(shape), jnp.abs(a.astype(jnp.float32)),
                         0).max()

    def padded(st, real):
        rows = [st.agents, st.ch_req, st.ch_resp, st.ch_hreq, st.ch_hresp]
        if st.dir.view.dtype == jnp.int8:   # dense [R, L] planes
            rows += [st.dir.view, st.hreq_pending]
        pad = jnp.arange(st.agents.remote_state.shape[0]) >= real
        return sum(((a != 0) & pad.reshape((-1,) + (1,) * (a.ndim - 1))
                    ).sum() for a in jax.tree_util.tree_leaves(rows))

    @jax.jit
    def extract(st, idx, touched, real=None):
        d, ag = st.dir, st.agents
        off = ~touched
        f32 = lambda a: a.astype(jnp.float32)
        dense_view = d.view.dtype == jnp.int8
        strays = (stray(d.home_state, 0, off) + stray(d.view, 1, off)
                  + stray(ag.remote_state, 1, off))
        if real is not None:
            strays = strays + padded(st, real)
        return {
            "home_state": jnp.take(d.home_state, idx, axis=0),
            "view": jnp.take(d.view, idx, axis=1) if dense_view else None,
            "remote_state": jnp.take(ag.remote_state, idx, axis=1),
            "cache": f32(jnp.take(ag.cache, idx, axis=1)),
            "home_buf": f32(jnp.take(d.home_buf, idx, axis=0)),
            "backing": f32(jnp.take(d.backing, idx, axis=0)),
            "stray": strays,
            "stray_data": jnp.maximum(jnp.maximum(
                off_max(ag.cache, 1, off), off_max(d.home_buf, 0, off)),
                off_max(d.backing, 0, off)),
            "illegal": d.illegal + ag.illegal.sum(),
        }
    return extract


class Target:
    """The system under test, driven through its public entry: an engine
    built from the configuration, and ``run_stream`` per point.  ``dtype``
    builds the engine's line data in another precision (the control)."""

    def __init__(self, config: dict, traffic: dict, dtype=None):
        from repro.traffic import (AdmissionConfig, EngineConfig,
                                   StreamConfig)
        cfg = EngineConfig(**config["engine"])
        self.engine = cfg.build()
        if dtype is not None:
            import jax.numpy as jnp
            eng = self.engine
            self.engine = type(eng)(
                jnp.zeros((cfg.lines, cfg.block), dtype),
                n_remotes=cfg.remotes, subset=eng.subset,
                delays=eng.delays, credits=eng.credits,
                shared_credits=cfg.shared_credits, n_homes=cfg.homes,
                home_bw=cfg.home_bw, kernel_backend=eng.kernel_backend,
                packed=cfg.packed)
        self.traffic = traffic
        self.remotes, self.lines = cfg.remotes, cfg.lines
        adm = traffic.get("admission")
        self._stream = lambda **kw: StreamConfig(
            admission=None if adm is None else AdmissionConfig(**adm),
            width=int(traffic.get("width", 1)), steps=0,
            collect_trace=True, **kw)
        self._extract = _extractor()

    def inputs(self, seed: int, point: int) -> List[traffic_gen.Inputs]:
        return [traffic_gen.generate(self.traffic, self.remotes, self.lines,
                                     seed, point)]

    def run(self, inputs: List[traffic_gen.Inputs]):
        from repro.traffic import ArrivalSchedule, Workload, run_stream
        (inp,) = inputs
        arr = (None if inp.arrival is None
               else ArrivalSchedule(inp.arrival))
        return [run_stream(self.engine, self._stream(
            workload=Workload(inp.op, inp.line, inp.value), arrivals=arr))]

    def extract(self, state, inputs: traffic_gen.Inputs):
        """Device arrays of the final state's touched lines (blocks until
        they are computed, so the state can go)."""
        return _extract(self._extract, state, inputs, self.lines)


def _extract(fn, state, inputs: traffic_gen.Inputs, n_lines: int,
             real: Optional[int] = None):
    import jax
    lines = np.unique(inputs.line[inputs.op != traffic_gen.NOP])
    idx = np.full(inputs.op.size, lines[0] if lines.size else 0, np.int32)
    idx[:lines.size] = lines
    touched = np.zeros(n_lines, bool)
    touched[lines] = True
    return lines, jax.block_until_ready(fn(
        state, idx, touched, None if real is None else np.int32(real)))


class FleetTarget:
    """A sweep as one program: the configuration's engine at each of
    ``fleet.remotes`` remotes, run as the members of one ``run_fleet``
    call per point, sharded over ``mesh_devices`` devices (0: all on
    one).  Each member's generated arrays reach ``run_fleet`` through the
    recipe it reads (``given_workload``).  ``dtype`` makes the fleet's
    fresh state, and so every member's line data, in another precision
    (the control): ``run_fleet`` builds that state itself, in float32."""

    def __init__(self, config: dict, traffic: dict, dtype=None,
                 mesh_devices: int = 0):
        from repro.traffic import EngineConfig, StreamConfig
        fleet, engine = config["fleet"], config["engine"]
        self.member_remotes = [int(r) for r in fleet["remotes"]]
        if max(self.member_remotes) != engine["remotes"]:
            raise ValueError(f"engine.remotes ({engine['remotes']}) must "
                             f"be the largest of fleet.remotes "
                             f"{self.member_remotes}")
        self.engines = [EngineConfig(**dict(engine, remotes=r))
                        for r in self.member_remotes]
        self.mesh_devices = int(mesh_devices)
        self.traffic, self.dtype = traffic, dtype
        self.lines = int(engine["lines"])
        self._stream = lambda wl: StreamConfig(
            workload=wl, width=int(traffic.get("width", 1)), steps=0,
            collect_trace=True)
        self._extract = _extractor()

    def inputs(self, seed: int, point: int) -> List[traffic_gen.Inputs]:
        return [traffic_gen.generate(self.traffic, r, self.lines, seed,
                                     point, member=m)
                for m, r in enumerate(self.member_remotes)]

    def run(self, inputs: List[traffic_gen.Inputs]):
        from unittest import mock

        import jax.numpy as jnp
        from repro.traffic import FleetConfig, Workload, fleet, run_fleet
        if any(i.arrival is not None for i in inputs):
            raise ValueError("a fleet runs closed-loop traffic only")
        members = tuple(
            (e, self._stream(given_workload()(
                ops=i.op.shape[0], given=Workload(i.op, i.line, i.value))))
            for e, i in zip(self.engines, inputs))
        config = FleetConfig(members=members,
                             mesh_devices=self.mesh_devices)
        if self.dtype is None:
            return run_fleet(config)
        real = fleet.make_engine_mn_state
        with mock.patch.object(
                fleet, "make_engine_mn_state",
                lambda backing, *a, **k: real(
                    jnp.asarray(backing, self.dtype), *a, **k)):
            return run_fleet(config)

    def extract(self, state, inputs: traffic_gen.Inputs):
        """As ``Target.extract``, on the chip that holds the member's
        state; its padded remotes' rows count under ``stray``."""
        return _extract(self._extract, state, inputs, self.lines,
                        real=inputs.op.shape[1])


@functools.lru_cache(maxsize=None)
def given_workload():
    """The class of a fleet member's generated arrays, in the form
    ``run_fleet`` takes a member's workload: a ``WorkloadSpec`` whose
    ``ops`` is T and whose ``materialize`` gives the arrays (``run_fleet``
    takes no concrete ``Workload``)."""
    import dataclasses

    from repro.traffic import WorkloadSpec

    @dataclasses.dataclass(frozen=True)
    class GivenWorkload(WorkloadSpec):
        given: object = None

        def materialize(self, n_remotes: int, n_lines: int):
            return self.given
    return GivenWorkload


def make_target(cell: Cell, dtype=None):
    """The target a cell's configuration asks for: a fleet where it holds
    a ``"fleet"`` group, sharded over the cell's chips, else one
    engine."""
    if "fleet" not in cell.config:
        return Target(cell.config, cell.traffic, dtype)
    return FleetTarget(cell.config, cell.traffic, dtype,
                       mesh_devices=cell.chips if cell.chips > 1 else 0)


class Member(NamedTuple):
    """One engine's share of a point: its inputs and what it returned."""

    inputs: traffic_gen.Inputs
    completed: bool
    retired: int
    steps: int
    active_steps: int
    retire_step: np.ndarray
    msg_count: np.ndarray
    lines: np.ndarray
    extracted: dict


class Point(NamedTuple):
    """One call of the program: one member, or a fleet's members."""

    members: List[Member]

    @property
    def retired(self) -> int:
        return sum(m.retired for m in self.members)

    @property
    def steps(self) -> int:
        """The steps the call scanned (every member scans the same)."""
        return self.members[0].steps

    @property
    def active_steps(self) -> int:
        return sum(m.active_steps for m in self.members)


def run_point(target, inputs: List[traffic_gen.Inputs]) -> Point:
    from jax.profiler import TraceAnnotation
    with TraceAnnotation(trace_reduce.POINT_SPAN):
        runs = target.run(inputs)
    members = []
    with TraceAnnotation("bench.extract"):
        for run, inp in zip(runs, inputs):
            lines, ex = target.extract(run.state, inp)
            ctr = run.counters
            members.append(Member(
                inp, bool(run.completed), int(np.sum(ctr.retired)),
                int(ctr.steps), int(ctr.active_steps),
                np.asarray(run.trace.retire_step),
                np.asarray(run.msg_count), lines, ex))
    return Point(members)


def member_output(m: Member) -> checks.PointOutput:
    import jax
    ex = jax.device_get(m.extracted)
    k, R = m.lines.size, m.inputs.op.shape[1]
    f64 = lambda a: np.asarray(a, np.float64)
    return checks.PointOutput(
        completed=m.completed, retired=m.retired, steps=m.steps,
        retire_step=m.retire_step, msg_count=m.msg_count, lines=m.lines,
        home_state=np.asarray(ex["home_state"])[:k],
        view=(None if ex["view"] is None
              else np.asarray(ex["view"])[:R, :k]),
        remote_state=np.asarray(ex["remote_state"])[:R, :k],
        cache=f64(ex["cache"])[:R, :k], home_buf=f64(ex["home_buf"])[:k],
        backing=f64(ex["backing"])[:k], stray=int(ex["stray"]),
        stray_data=float(ex["stray_data"]), illegal=int(ex["illegal"]))


class Window(NamedTuple):
    points: List[Point]
    start: float
    end: float
    compiles: int
    trace: Optional[trace_reduce.Summary]


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def measure(target, cell: Cell, seed: int, seconds: float,
            trace: bool, clock: CompileClock) -> Window:
    """Points back to back for about ``seconds``: the first point's time
    sets how many fill the window (at least one), so the window is within
    half a point of ``seconds``.  It runs from the first point's start to
    the last point's return."""
    import jax
    from jax.profiler import TraceAnnotation
    tmp = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        if trace:
            jax.profiler.start_trace(tmp, profiler_options=_profile_options())
        points = []
        with TraceAnnotation(trace_reduce.WINDOW_SPAN):
            start = time.perf_counter()
            count = None
            while count is None or len(points) < count:
                with TraceAnnotation("bench.generate"):
                    inputs = target.inputs(seed, len(points))
                points.append(run_point(target, inputs))
                if count is None:
                    first = time.perf_counter() - start
                    count = max(1, round(seconds / first))
            end = time.perf_counter()
        summary = None
        if trace:
            jax.profiler.stop_trace()
            found = sorted(Path(tmp).rglob("*.xplane.pb"))
            if found:
                summary = trace_reduce.summarize(
                    trace_reduce.load(str(found[-1])))
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)
    return Window(points, start, end, clock.count(start, end), summary)


class RunData(NamedTuple):
    """What a per-layer reader reads."""

    points: List[Point]
    window_s: float
    trace: Optional[trace_reduce.Summary]


def end_to_end(cell: Cell, win: Window, setup_s: float,
               peak_bytes: int) -> Dict[str, dict]:
    values = {
        "sim_ops_per_s": sum(p.retired for p in win.points)
        / (win.end - win.start),
        "peak_hbm_gb": peak_bytes / 1e9,
        "setup_s": setup_s,
    }
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}


def per_layer(cell: Cell, win: Window) -> Dict[str, dict]:
    data = RunData(win.points, win.end - win.start, win.trace)
    out = {}
    for m in cell.per_layer:
        value = load_module("metrics", m["name"]).read(data)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             devices, t0: float,
             target_factory: Optional[Callable[[], object]] = None,
             hbm_bytes: Optional[int] = None, warm_up: bool = True) -> dict:
    """Set-up, window and check of one run; returns the result line.
    ``warm_up=False`` skips the warm-up point, for a process that has
    already run the cell's programs."""
    clock = CompileClock()
    check_interface()
    reference = load_module("references", cell.config["reference"])
    target = (target_factory or (lambda: make_target(cell)))()
    if warm_up:   # compiles the stream and the read-back programs
        run_point(target, target.inputs(seed, -1))
    setup_s = time.perf_counter() - t0
    win = measure(target, cell, seed, seconds, trace, clock)
    peak = peak_bytes(devices)
    outputs = [[member_output(m) for m in p.members] for p in win.points]
    del target
    numbers = [[checks.compare_point(m.inputs, o, reference)
                for m, o in zip(p.members, out)]
               for p, out in zip(win.points, outputs)]
    total = checks.combine([n for point in numbers for n in point])
    dev = devices[0]
    result = {
        "correct": checks.verdict(total),
        "attempted": len(win.points),
        "failed": sum(not all(map(checks.verdict, n)) for n in numbers),
        "metrics": (per_layer(cell, win) if trace else
                    end_to_end(cell, win, setup_s, peak)),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": peak},
    }
    if trace and win.trace is not None:
        result["device"].update(busy_s=win.trace.busy_s,
                                window_s=win.trace.window_s)
        result["breakdown"] = trace_reduce.breakdown(win.trace)
    result["checks"] = checks.report(total)
    print(json.dumps({"cell": cell.name, "seed": seed,
                      "points": len(win.points),
                      "window_s": win.end - win.start,
                      "compiles_in_window": win.compiles,
                      "steps_per_point": win.points[0].steps,
                      "active_steps": [p.active_steps for p in win.points],
                      "point_ops": [p.retired for p in win.points],
                      "hbm_fill": peak / hbm_bytes if hbm_bytes else None}),
          file=sys.stderr)
    for name, v in result["checks"].items():
        print(f"check {name} {v['value']} limit {v['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    return result
