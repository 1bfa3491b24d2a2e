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
"""
from __future__ import annotations

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
    state, and what lies off them."""
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

    @jax.jit
    def extract(st, idx, touched):
        d, ag = st.dir, st.agents
        off = ~touched
        f32 = lambda a: a.astype(jnp.float32)
        dense_view = d.view.dtype == jnp.int8
        return {
            "home_state": jnp.take(d.home_state, idx, axis=0),
            "view": jnp.take(d.view, idx, axis=1) if dense_view else None,
            "remote_state": jnp.take(ag.remote_state, idx, axis=1),
            "cache": f32(jnp.take(ag.cache, idx, axis=1)),
            "home_buf": f32(jnp.take(d.home_buf, idx, axis=0)),
            "backing": f32(jnp.take(d.backing, idx, axis=0)),
            "stray": (stray(d.home_state, 0, off)
                      + stray(d.view, 1, off)
                      + stray(ag.remote_state, 1, off)),
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
        self.remotes, self.lines = cfg.remotes, cfg.lines
        adm = traffic.get("admission")
        self._stream = lambda **kw: StreamConfig(
            admission=None if adm is None else AdmissionConfig(**adm),
            width=int(traffic.get("width", 1)), steps=0,
            collect_trace=True, **kw)
        self._extract = _extractor()

    def run(self, inputs: traffic_gen.Inputs):
        from repro.traffic import ArrivalSchedule, Workload, run_stream
        arr = (None if inputs.arrival is None
               else ArrivalSchedule(inputs.arrival))
        return run_stream(self.engine, self._stream(
            workload=Workload(inputs.op, inputs.line, inputs.value),
            arrivals=arr))

    def extract(self, state, inputs: traffic_gen.Inputs):
        """Device arrays of the final state's touched lines (blocks until
        they are computed, so the state can go)."""
        import jax
        lines = np.unique(inputs.line[inputs.op != traffic_gen.NOP])
        idx = np.full(inputs.op.size, lines[0] if lines.size else 0,
                      np.int32)
        idx[:lines.size] = lines
        touched = np.zeros(self.lines, bool)
        touched[lines] = True
        return lines, jax.block_until_ready(
            self._extract(state, idx, touched))


class Point(NamedTuple):
    inputs: traffic_gen.Inputs
    completed: bool
    retired: int
    steps: int
    active_steps: int
    retire_step: np.ndarray
    msg_count: np.ndarray
    lines: np.ndarray
    extracted: dict


def run_point(target: Target, inputs: traffic_gen.Inputs) -> Point:
    from jax.profiler import TraceAnnotation
    with TraceAnnotation(trace_reduce.POINT_SPAN):
        run = target.run(inputs)
    with TraceAnnotation("bench.extract"):
        lines, ex = target.extract(run.state, inputs)
    ctr = run.counters
    return Point(inputs, bool(run.completed), int(np.sum(ctr.retired)),
                 int(ctr.steps), int(ctr.active_steps),
                 np.asarray(run.trace.retire_step), np.asarray(run.msg_count),
                 lines, ex)


def point_output(p: Point) -> checks.PointOutput:
    import jax
    ex = jax.device_get(p.extracted)
    k = p.lines.size
    f64 = lambda a: np.asarray(a, np.float64)
    return checks.PointOutput(
        completed=p.completed, retired=p.retired, steps=p.steps,
        retire_step=p.retire_step, msg_count=p.msg_count, lines=p.lines,
        home_state=np.asarray(ex["home_state"])[:k],
        view=None if ex["view"] is None else np.asarray(ex["view"])[:, :k],
        remote_state=np.asarray(ex["remote_state"])[:, :k],
        cache=f64(ex["cache"])[:, :k], home_buf=f64(ex["home_buf"])[:k],
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


def measure(target: Target, cell: Cell, seed: int, seconds: float,
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
                    inputs = traffic_gen.generate(
                        cell.traffic, target.remotes, target.lines, seed,
                        len(points))
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
             target_factory: Optional[Callable[[], Target]] = None,
             hbm_bytes: Optional[int] = None, warm_up: bool = True) -> dict:
    """Set-up, window and check of one run; returns the result line.
    ``warm_up=False`` skips the warm-up point, for a process that has
    already run the cell's programs."""
    clock = CompileClock()
    check_interface()
    reference = load_module("references", cell.config["reference"])
    target = (target_factory or (lambda: Target(cell.config,
                                                cell.traffic)))()
    if warm_up:   # compiles the stream and the read-back programs
        run_point(target, traffic_gen.generate(
            cell.traffic, target.remotes, target.lines, seed, -1))
    setup_s = time.perf_counter() - t0
    win = measure(target, cell, seed, seconds, trace, clock)
    peak = peak_bytes(devices)
    outputs = [point_output(p) for p in win.points]
    del target
    numbers = [checks.compare_point(p.inputs, o, reference)
               for p, o in zip(win.points, outputs)]
    total = checks.combine(numbers)
    dev = devices[0]
    result = {
        "correct": checks.verdict(total),
        "attempted": len(win.points),
        "failed": sum(not checks.verdict(n) for n in numbers),
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
