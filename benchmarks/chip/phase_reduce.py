"""Per-phase reduction of a profiler trace of the stream program.

The program names the phases of its fused scan with ``jax.named_scope``
(``eci.issue``, ``eci.step`` and the step's ``eci.transport``,
``eci.credit_rank``, ``eci.arbitrate``, ``eci.directory``,
``eci.agents``; then ``eci.retire``, ``eci.counters``, ``eci.observe``)
and its host path with ``TraceAnnotation`` spans (``eci.prepare``,
``eci.dispatch``, ``eci.readback``).  This module reads both from the
same trace ``trace_reduce`` reads, on the same clock and window.

How a device op gets its phase (``hlo_phases``, from the compiled stream
program's HLO text, keyed by instruction name, which is what the trace's
``XLA Ops`` events are named by):

* the innermost ``eci.*`` component of the instruction's own
  ``op_name`` metadata: a ranking called from the fan-out is
  ``eci.credit_rank``, not ``eci.directory``.  A fusion carries its root
  op's ``op_name``;
* an instruction with no metadata at all (the compiler made it: a
  scatter rewritten into a custom fusion, an asynchronous copy) takes
  the phase of its fused computation's instructions, else of the nearest
  instructions that consume its result;
* an instruction whose ``op_name`` names no ``eci.*`` scope is unphased:
  the loop's own bookkeeping, carry copies, the program's set-up.

Per phase, the device time is the summed duration of the stream
module's ops of that phase inside the window, averaged over devices.
The stream module's device time splits exactly into the phases, the
unphased ops and the scan's waits (time inside the module in which no
op ran, such as a wait on an asynchronous copy), as long as no two ops
overlap on a device.  ``eci.step``'s own time is the step's glue outside
its named phases.
"""
from __future__ import annotations

import re
from collections import Counter
from typing import Dict, List, NamedTuple, Optional, Tuple

import trace_reduce as tr

#: device phases of the stream program, in scan order.
PHASES = ("eci.issue", "eci.step", "eci.transport", "eci.credit_rank",
          "eci.arbitrate", "eci.directory", "eci.agents", "eci.retire",
          "eci.counters", "eci.observe")
#: the program's host spans around one ``run_stream`` call, in order.
HOST_SPANS = ("eci.prepare", "eci.dispatch", "eci.readback")
UNPHASED = "unphased"

_COMP = re.compile(r"^(?:ENTRY )?%([\w.\-]+) ")
_INSTR = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")


def innermost(op_name: str) -> Optional[str]:
    """The innermost ``eci.*`` component of an ``op_name``, if any."""
    found = [p for p in op_name.split("/") if p.startswith("eci.")]
    return found[-1] if found else None


class _Instr(NamedTuple):
    comp: str
    op_name: Optional[str]
    calls: Optional[str]
    operands: List[str]


def _parse(hlo: str) -> Dict[str, _Instr]:
    instrs: Dict[str, _Instr] = {}
    comp = ""
    for line in hlo.splitlines():
        m = _COMP.match(line)
        if m and line.rstrip().endswith("{"):
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        rest = line[m.end():]
        meta = _OP_NAME.search(rest)
        calls = _CALLS.search(rest)
        head = rest.split(", metadata=")[0]
        instrs[m.group(1)] = _Instr(comp, meta.group(1) if meta else None,
                                    calls.group(1) if calls else None,
                                    _REF.findall(head))
    return instrs


def hlo_phases(hlo: str) -> Dict[str, Optional[str]]:
    """Instruction name -> phase (``None`` for unphased) for every
    instruction of a compiled module's HLO text."""
    instrs = _parse(hlo)
    by_comp: Dict[str, List[str]] = {}
    users: Dict[str, List[str]] = {}
    for name, ins in instrs.items():
        by_comp.setdefault(ins.comp, []).append(name)
        for ref in ins.operands:
            if ref in instrs and instrs[ref].comp == ins.comp:
                users.setdefault(ref, []).append(name)

    def own(name):
        op = instrs[name].op_name
        return innermost(op) if op else None

    def called(name):
        comp = instrs[name].calls
        votes = Counter(p for p in map(own, by_comp.get(comp, ())) if p)
        return votes.most_common(1)[0][0] if votes else None

    def consumers(name):
        seen, frontier = {name}, [name]
        while frontier:
            found, nxt = [], []
            for n in frontier:
                for u in users.get(n, ()):
                    if u in seen:
                        continue
                    seen.add(u)
                    p = own(u)
                    if p:
                        found.append(p)
                    elif instrs[u].op_name is None:
                        nxt.append(u)
            if found:
                return Counter(found).most_common(1)[0][0]
            frontier = nxt
        return None

    out = {}
    for name, ins in instrs.items():
        if ins.op_name is not None:
            out[name] = innermost(ins.op_name)
        else:
            out[name] = called(name) or consumers(name)
    return out


def _instr(event_name: str) -> str:
    return tr.op_name(event_name).split(" ")[0]


class PhaseSummary(NamedTuple):
    window_s: float
    stream_module: Optional[str]
    stream_device_s: float       # mean over devices, inside the window
    phase_s: Dict[str, float]    # device s per phase, mean over devices
    unphased_s: float            # stream-module ops with no phase
    scan_wait_s: float           # idle inside the stream module
    idle_phases: Dict[str, float]   # window idle s by what it waited on
    point_host: List[Dict[str, float]]  # per point: host span s, and
    #                                     the readback s no stream device
    #                                     time covers
    phase_ops: Dict[str, List[Tuple[str, float]]]  # top ops per phase


def summarize(planes: List[tr.Plane], phase_of: Dict[str, Optional[str]],
              base: Optional[tr.Summary] = None) -> Optional[PhaseSummary]:
    """The window's per-phase numbers, or None where the trace holds no
    window or no device operation.  ``base`` is ``trace_reduce``'s
    summary of the same trace, where the caller has it."""
    base = base or tr.summarize(planes)
    if base is None:
        return None
    host = tr.bench_thread(planes)
    win = max((e for e in host if e.name == tr.WINDOW_SPAN),
              key=lambda e: e.dur)
    lo, hi = win.start, win.end
    marks = [e for e in host if e.name.startswith(("eci.", "bench."))]
    devs = tr.device_planes(planes)
    n = len(devs)
    phase_ns: Counter = Counter()
    op_ns: Dict[str, Counter] = {}
    wait_ns, idle_ns = 0, Counter()
    streams = []
    for plane in devs:
        mods = tr.union(((e.start, e.end) for e in tr._line(
            plane, tr.MODULES_LINE) if e.name == base.stream_module), lo, hi)
        streams.append([tr.Event(base.stream_module, s, e - s)
                        for s, e in mods])
        ops = tr.leaves(tr._line(plane, tr.OPS_LINE))
        inside = []
        for e in ops:
            t = sum(max(0, min(e.end, me) - max(e.start, ms))
                    for ms, me in mods)
            if t:
                inside.append((e, t))
                phase = phase_of.get(_instr(e.name)) or UNPHASED
                phase_ns[phase] += t
                op_ns.setdefault(phase, Counter())[tr.op_name(e.name)] += t
        for ms, me in mods:
            busy = tr.union(((e.start, e.end) for e, _ in inside), ms, me)
            wait_ns += (me - ms) - sum(b - a for a, b in busy)
        # each idle gap: the asynchronous op in flight over its middle,
        # inside the stream module, else the innermost program or
        # benchmark span over it
        gaps = tr.gaps(tr.union(((e.start, e.end) for e in ops), lo, hi),
                       lo, hi)
        mids = [(a + b) // 2 for a, b in gaps]
        waits = _covering(tr._line(plane, tr.ASYNC_LINE), mids)
        for (a, b), mid, wait, span in zip(gaps, mids, waits,
                                           _covering(marks, mids)):
            if wait is not None and any(ms <= mid < me for ms, me in mods):
                key = "async " + (phase_of.get(_instr(wait.name))
                                  or UNPHASED)
            else:
                key = span.name if span else tr.WINDOW_SPAN
            idle_ns[key] += b - a
    point_host = []
    for spans in host_spans(host, lo, hi):
        rec = {name: spans[name].dur / 1e9 if name in spans else 0.0
               for name in HOST_SPANS}
        rb = spans.get("eci.readback")
        covered = (sum(tr.overlap(s, rb.start, rb.end) for s in streams)
                   / n if rb else 0)
        rec["readback_uncovered"] = ((rb.dur - covered) / 1e9 if rb
                                     else 0.0)
        point_host.append(rec)
    unphased = phase_ns.pop(UNPHASED, 0)
    return PhaseSummary(
        window_s=base.window_s, stream_module=base.stream_module,
        stream_device_s=base.stream_device_s,
        phase_s={p: phase_ns[p] / n / 1e9 for p in PHASES if p in phase_ns},
        unphased_s=unphased / n / 1e9, scan_wait_s=wait_ns / n / 1e9,
        idle_phases={k: v / n / 1e9 for k, v in idle_ns.most_common()},
        point_host=point_host,
        phase_ops={p: [(name, t / n / 1e9)
                       for name, t in c.most_common(5)]
                   for p, c in op_ns.items()})


def host_spans(host: List[tr.Event], lo: int, hi: int
               ) -> List[Dict[str, tr.Event]]:
    """For each ``bench.run_stream`` span inside ``[lo, hi]``, in order,
    the program's host spans (``HOST_SPANS``) inside it, by name."""
    points = sorted((e for e in host if e.name == tr.POINT_SPAN
                     and lo <= e.start and e.end <= hi),
                    key=lambda e: e.start)
    return [{e.name: e for e in host if e.name in HOST_SPANS
             and p.start <= e.start and e.end <= p.end} for p in points]


def _covering(events: List[tr.Event], points: List[int]
              ) -> List[Optional[tr.Event]]:
    """For each of the ascending ``points``, the shortest of ``events``
    that covers it, or None: one sweep over the events by start."""
    order = sorted(events, key=lambda e: e.start)
    out, live, i = [], [], 0
    for p in points:
        while i < len(order) and order[i].start <= p:
            live.append(order[i])
            i += 1
        live = [e for e in live if e.end > p]
        out.append(min(live, key=lambda e: e.dur) if live else None)
    return out


#: the per-step phase metrics and the phase each reads.
PHASE_METRICS = {
    "issue_ms_per_step": "eci.issue",
    "retire_ms_per_step": "eci.retire",
    "counters_ms_per_step": "eci.counters",
    "transport_ms_per_step": "eci.transport",
    "credit_rank_ms_per_step": "eci.credit_rank",
    "arbitrate_ms_per_step": "eci.arbitrate",
    "directory_ms_per_step": "eci.directory",
    "agents_ms_per_step": "eci.agents",
}


def metrics(s: Optional[PhaseSummary], steps: int
            ) -> Optional[Dict[str, float]]:
    """The per-layer numbers of a window that scanned ``steps`` steps:
    each phase's device ms per step, the unphased share of the stream
    module's op time, the scan's waits per step, and the host time per
    point that no stream device time covers."""
    if s is None or not steps or not s.stream_device_s:
        return None
    out = {m: 1e3 * s.phase_s.get(p, 0.0) / steps
           for m, p in PHASE_METRICS.items()}
    op_s = sum(s.phase_s.values()) + s.unphased_s
    out["unphased_device_share"] = 100.0 * s.unphased_s / op_s \
        if op_s else 0.0
    out["scan_wait_ms_per_step"] = 1e3 * s.scan_wait_s / steps
    if s.point_host:
        out["stream_host_ms_per_point"] = 1e3 * sum(
            p["eci.prepare"] + p["eci.dispatch"] + p["readback_uncovered"]
            for p in s.point_host) / len(s.point_host)
    return out


def identity(s: PhaseSummary, steps: int) -> Dict[str, float]:
    """Per step: every phase (``eci.step``'s glue and ``eci.observe``
    too), the unphased ops and the scan's waits, against the stream
    module's device time; ``gap`` is their relative difference."""
    parts = 1e3 * (sum(s.phase_s.values()) + s.unphased_s
                   + s.scan_wait_s) / steps
    whole = 1e3 * s.stream_device_s / steps
    return {"parts_ms": parts, "step_device_ms": whole,
            "gap": (parts - whole) / whole if whole else 0.0}


def breakdown(s: PhaseSummary) -> Dict[str, list]:
    return {"phases": [[p, t] for p, t in s.phase_s.items()]
            + [[UNPHASED, s.unphased_s], ["scan_wait", s.scan_wait_s]],
            "idle_phases": [[k, v] for k, v in s.idle_phases.items()]}
