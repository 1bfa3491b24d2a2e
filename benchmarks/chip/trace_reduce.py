"""Reduction of a profiler trace to the benchmark's device numbers.

The input is the JAX profiler's ``.xplane.pb``, read with
``jax.profiler.ProfileData`` into plain ``Plane``/``Line``/``Event``
tuples (``load``), so the arithmetic below also runs on synthetic traces
(``test_trace_reduce.py``).  All times are nanoseconds on the trace's
one clock.

* Device planes are the planes named ``/device:<platform>:<n>``; their
  operations are the events of the ``XLA Ops`` line that enclose no
  other event there (a ``while`` op spans the whole loop its body's ops
  run in, and is left out), their programs the events of the
  ``XLA Modules`` line.  An operation is named by its HLO name and its
  first result type (``fusion.12 s32[192]``), not its whole HLO text.
  The events of the ``Async XLA Ops`` line (copies and slices in flight,
  ``copy-start`` to ``copy-done``) are not busy time: while the device
  waits on one, no operation runs.
* Busy time is the union of a device's operation intervals inside the
  window (the benchmark's ``bench.window`` host span); the idle share is
  one minus busy over the window, averaged over the devices.
* The stream program is the module that takes the most device time in
  the window; its device time inside each ``bench.run_stream`` span is
  what the host did not spend.
* Each idle gap is named by what covers its middle: the asynchronous
  device op in flight there, if any (``async <op>``), else the innermost
  host event, under the benchmark span it fell in.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
POINT_SPAN = "bench.run_stream"
_DEVICE_PLANE = re.compile(r"^/device:[A-Za-z]+:\d+$")


class Event(NamedTuple):
    name: str
    start: int      # ns
    dur: int        # ns

    @property
    def end(self) -> int:
        return self.start + self.dur


class Line(NamedTuple):
    name: str
    events: List[Event]


class Plane(NamedTuple):
    name: str
    lines: List[Line]


def load(path: str) -> List[Plane]:
    """Read an ``.xplane.pb`` into plain tuples."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    return [Plane(p.name, [Line(ln.name, [Event(e.name, int(e.start_ns),
                                                int(e.duration_ns))
                                          for e in ln.events])
                           for ln in p.lines])
            for p in pd.planes]


def device_planes(planes: Iterable[Plane]) -> List[Plane]:
    return [p for p in planes if _DEVICE_PLANE.match(p.name)
            and any(ln.name == OPS_LINE for ln in p.lines)]


def bench_thread(planes: Iterable[Plane]) -> List[Event]:
    """The events of the host thread that holds the longest window span:
    the benchmark's spans and what the program did on that thread."""
    best, events = -1, []
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for e in ln.events:
                if e.name == WINDOW_SPAN and e.dur > best:
                    best, events = e.dur, ln.events
    return events


def _line(plane: Plane, name: str) -> List[Event]:
    return [e for ln in plane.lines if ln.name == name for e in ln.events]


def leaves(events: Iterable[Event]) -> List[Event]:
    """The events that enclose no other.  A container (a ``while``)
    starts no later than its first enclosed op, so it encloses another
    event exactly when the next event to start lies inside it."""
    ordered = sorted(events, key=lambda e: (e.start, -e.dur))
    return [e for e, nxt in zip(ordered, ordered[1:] + [None])
            if nxt is None or nxt.start >= e.end or nxt.end > e.end]


_RESULT = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")


def op_name(hlo: str) -> str:
    """``%fusion.12 = s32[192]{0} fusion(...)`` -> ``fusion.12 s32[192]``."""
    head, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo
    m = _RESULT.search(rest)
    return head.lstrip("%") + (" " + m.group(0) if m else "")


def union(intervals: Iterable[Tuple[int, int]], lo: int, hi: int
          ) -> List[Tuple[int, int]]:
    """Merged intervals, clipped to ``[lo, hi]``."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def overlap(events: Iterable[Event], lo: int, hi: int) -> int:
    """Summed overlap of ``events`` with ``[lo, hi]``."""
    return sum(max(0, min(e.end, hi) - max(e.start, lo)) for e in events)


def gaps(busy: List[Tuple[int, int]], lo: int, hi: int
         ) -> List[Tuple[int, int]]:
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def name_gap(gap: Tuple[int, int], host: List[Event],
             in_flight: List[Event] = ()) -> str:
    """``<bench span>/<what covers the gap's middle>``: the innermost
    asynchronous device op in flight (``async <op>``), else the innermost
    host event."""
    mid = (gap[0] + gap[1]) // 2
    over = [e for e in host if e.start <= mid < e.end]
    bench = [e for e in over if e.name.startswith("bench.")
             and e.name != WINDOW_SPAN]
    outer = min(bench, key=lambda e: e.dur).name if bench else WINDOW_SPAN
    waits = [e for e in in_flight if e.start <= mid < e.end]
    if waits:
        return outer + "/async " + op_name(min(waits,
                                               key=lambda e: e.dur).name)
    inner = [e for e in over if not e.name.startswith("bench.")]
    if not inner:
        return outer
    return outer + "/" + min(inner, key=lambda e: e.dur).name


class Summary(NamedTuple):
    window_s: float
    busy_s: float                # mean over devices
    stream_module: Optional[str]
    stream_device_s: float       # mean over devices, inside the window
    point_spans: List[Tuple[float, float]]  # (host s, stream device s)
    device_ops: List[Tuple[str, float]]     # top 10 by time
    idle_gaps: List[Tuple[str, float]]      # longest 10


def summarize(planes: List[Plane]) -> Optional[Summary]:
    """The window's numbers, or None where the trace holds no window or
    no device operation (nothing to read)."""
    host = bench_thread(planes)
    windows = [e for e in host if e.name == WINDOW_SPAN]
    devs = device_planes(planes)
    if not windows or not devs:
        return None
    win = max(windows, key=lambda e: e.dur)
    lo, hi = win.start, win.end
    points = sorted((e for e in host if e.name == POINT_SPAN
                     and lo <= e.start and e.end <= hi),
                    key=lambda e: e.start)

    busy_ns, ops, mods, all_gaps = 0, {}, {}, []
    per_dev_modules = []
    for plane in devs:
        op_events = leaves(_line(plane, OPS_LINE))
        busy = union(((e.start, e.end) for e in op_events), lo, hi)
        busy_ns += sum(e - s for s, e in busy)
        in_flight = _line(plane, ASYNC_LINE)
        all_gaps.extend((g, in_flight) for g in gaps(busy, lo, hi))
        for e in op_events:
            inside = overlap([e], lo, hi)
            if inside:
                name = op_name(e.name)
                ops[name] = ops.get(name, 0) + inside
        mod_events = _line(plane, MODULES_LINE)
        per_dev_modules.append(mod_events)
        for e in mod_events:
            inside = overlap([e], lo, hi)
            if inside:
                mods[e.name] = mods.get(e.name, 0) + inside
    n = len(devs)
    stream = max(mods, key=mods.get) if mods else None
    streams = [[e for e in m if e.name == stream] for m in per_dev_modules]
    point_spans = [
        (p.dur / 1e9, sum(overlap(s, p.start, p.end) for s in streams)
         / n / 1e9) for p in points]
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    longest = sorted(all_gaps, key=lambda g: g[0][0] - g[0][1])[:10]
    return Summary(
        window_s=(hi - lo) / 1e9,
        busy_s=busy_ns / n / 1e9,
        stream_module=stream,
        stream_device_s=(mods.get(stream, 0) / n / 1e9) if stream else 0.0,
        point_spans=point_spans,
        device_ops=[[name, ns / n / 1e9] for name, ns in top_ops],
        idle_gaps=[[name_gap(g, host, waits), (g[1] - g[0]) / 1e9]
                   for g, waits in longest])


def breakdown(summary: Summary) -> Dict[str, list]:
    return {"device_ops": [list(x) for x in summary.device_ops],
            "idle_gaps": [list(x) for x in summary.idle_gaps]}
