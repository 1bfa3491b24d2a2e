"""The comparison that decides ``correct``.

Every point of a run is compared with the configuration's plain
reference (``references/<name>.py``), replayed over the point's own
generated inputs in the order in which the program retired them.  Five
numbers come out, summed (or, for data, maximised) over the points, and
each is held to its limit:

* ``incomplete``: points that did not drain, plus ops never retired;
* ``order``: ops retired at or past the step budget, before their
  arrival, or not after the previous op of the same remote on the same
  line (per-line program order);
* ``messages``: the distance between the program's per-type message
  counts and the reference's, after the one legal divergence: an upgrade
  that lost a race costs one extra ``REQ_UPGRADE`` + ``RESP_NACK``.  The
  reference bounds how many such pairs are legal: one for each store
  whose remote lost its shared copy of the line to another remote's
  store after the store could have been issued (once the remote's
  previous op on that line had retired), and before it retired; pairs
  past that bound count;
* ``states``: line states (home, each remote's state and the directory's
  view of it) that differ from the reference, plus entries that are not
  zero on lines the point never touched, plus the protocol's illegal-
  transition flags;
* ``data``: the largest gap between a data word the home or an agent
  holds at the end and the reference's value (backing store, the home's
  buffer where the home holds the line, each agent's copy where it holds
  one), and the largest data word on an untouched line.

All five are exact comparisons, so every limit is 0 (PERF.md gives the
readings).
"""
from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional

import numpy as np

STORE = 2

#: each number's limit: a run is correct when no number exceeds it.
LIMITS: Dict[str, float] = {"incomplete": 0, "order": 0, "messages": 0,
                            "states": 0, "data": 0.0}

#: the directory's view codes (I, S, E-or-M) of each remote state.
_VIEW_OF_REMOTE = np.asarray([0, 1, 2, 2], np.int64)


class PointOutput(NamedTuple):
    """What the program returned for one point, read back to the host."""

    completed: bool
    retired: int                 # ops the counters saw retire
    steps: int                   # the step budget the scan ran
    retire_step: np.ndarray      # [T, R] step each op retired, -1 = never
    msg_count: np.ndarray        # [16] messages delivered by type
    lines: np.ndarray            # [K] touched lines (sorted, unique)
    home_state: np.ndarray       # [K]
    view: Optional[np.ndarray]   # [R, K] (None for packed planes)
    remote_state: np.ndarray     # [R, K]
    cache: np.ndarray            # [R, K, B]
    home_buf: np.ndarray         # [K, B]
    backing: np.ndarray          # [K, B]
    stray: int                   # non-zero state entries off the touched lines
    stray_data: float            # largest |data| off the touched lines
    illegal: int                 # illegal-transition flags raised


def replay_order(inputs, retire_step: np.ndarray):
    """``(t, r)`` of every retired op, in retirement order: by step, then
    remote, then program order.  Per line the program serializes
    transactions, and retirements on one line within one step can only be
    loads, which commute."""
    rs = np.asarray(retire_step)
    tt, rr = np.nonzero((rs >= 0) & (inputs.op != 0))
    order = np.lexsort((tt, rr, rs[tt, rr]))
    return list(zip(tt[order].tolist(), rr[order].tolist()))


def replay(inputs, retire_step: np.ndarray, reference):
    """The reference replayed in the program's retirement order, and the
    number of stores whose upgrade may legally have been NACKed: those
    whose remote lost its shared copy of the line to another remote's
    store after its own last op on that line retired.  (A remote keeps
    one transaction per line in flight, so a store may issue from S as
    soon as the remote's previous op on the line has retired.)"""
    d = reference.Directory(inputs.op.shape[1])
    lost = set()      # (remote, line): S copy taken since its last op
    racing = 0
    for t, r in replay_order(inputs, retire_step):
        op, line = int(inputs.op[t, r]), int(inputs.line[t, r])
        racing += op == STORE and (r, line) in lost
        lost.discard((r, line))
        shared = [n for n in range(d.n_remotes) if n != r
                  and d.holds(n, line) == reference.R_S]
        d.apply(op, r, line, float(inputs.value[t, r]))
        lost.update((n, line) for n in shared
                    if d.holds(n, line) == reference.R_I)
    return d, racing

def order_faults(inputs, out: PointOutput) -> int:
    rs = np.asarray(out.retire_step, np.int64)
    real = inputs.op != 0
    done = real & (rs >= 0)
    bad = int((done & (rs >= out.steps)).sum())
    if inputs.arrival is not None:
        bad += int((done & (rs < inputs.arrival)).sum())
    T, R = inputs.op.shape
    for r in range(R):
        last: Dict[int, int] = {}
        for t in range(T):
            if not done[t, r]:
                continue
            line = int(inputs.line[t, r])
            if line in last and rs[t, r] <= last[line]:
                bad += 1
            last[line] = int(rs[t, r])
    return bad


def compare_point(inputs, out: PointOutput, reference) -> Dict[str, float]:
    """The five numbers of one point (``reference`` is the module of the
    configuration's plain reference)."""
    real = inputs.op != 0
    n_real = int(real.sum())
    never = int((real & (np.asarray(out.retire_step) < 0)).sum())
    incomplete = int(not out.completed) + never + abs(out.retired - n_real)

    ref, racing = replay(inputs, out.retire_step, reference)
    eng = np.asarray(out.msg_count, np.int64)
    expect = np.asarray(ref.counts, np.int64)
    nacks = min(int(eng[reference.MSG["RESP_NACK"]]), racing)
    expect[reference.MSG["REQ_UPGRADE"]] += nacks
    expect[reference.MSG["RESP_NACK"]] += nacks
    messages = int(np.abs(eng - expect).sum())

    R = out.remote_state.shape[0]
    states = int(out.stray) + int(out.illegal)
    data = float(out.stray_data)
    for k, line in enumerate(np.asarray(out.lines).tolist()):
        ln = ref.lines.get(line) or reference.Line()
        states += int(out.home_state[k] != ln.home)
        data = max(data, float(np.abs(out.backing[k] - ln.backing).max()))
        if ln.home != reference.H_I:
            data = max(data, float(np.abs(out.home_buf[k]
                                          - ln.home_buf).max()))
        want = np.zeros(R, np.int64)
        for node, s in ln.remote.items():
            want[node] = s
        states += int((out.remote_state[:, k] != want).sum())
        if out.view is not None:
            states += int((out.view[:, k] != _VIEW_OF_REMOTE[want]).sum())
        for node, value in ln.cache.items():
            data = max(data, float(np.abs(out.cache[node, k]
                                          - value).max()))
    return {"incomplete": incomplete, "order": order_faults(inputs, out),
            "messages": messages, "states": states, "data": data}


def combine(per_point: List[Dict[str, float]]) -> Dict[str, float]:
    """A run's numbers: counts summed over its points, data maximised."""
    out = {k: 0 for k in LIMITS}
    out["data"] = 0.0
    for p in per_point:
        for k, v in p.items():
            out[k] = max(out[k], v) if k == "data" else out[k] + v
    return out


def verdict(numbers: Dict[str, float]) -> bool:
    return all(numbers[k] <= LIMITS[k] for k in LIMITS)


def report(numbers: Dict[str, float]) -> Dict[str, dict]:
    """Each number beside its limit, for the result line and stderr."""
    return {k: {"value": numbers[k], "limit": LIMITS[k]} for k in LIMITS}
