"""Plain reference of a MOESI home directory: one home, R caching agents,
every transaction atomic.

It replays loads and stores in a given order and returns the messages
that order costs and the state every line ends in.  It follows the
paper's protocol (ECI, arXiv 2208.07124, Fig. 1 and Table 1) with the
full-map sharer directory of the multi-node extension: a store
invalidates every other sharer, a load demotes an exclusive owner to S,
a dirty owner demoted to S leaves the home in the hidden O state, E->M
is silent.  Lines are kept in dicts, so only the lines a run touches
cost anything.  It imports nothing of the program under test.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

#: home states (I, S, E, M and the hidden O)
H_I, H_S, H_E, H_M, H_O = 0, 1, 2, 3, 4
#: remote states
R_I, R_S, R_E, R_M = 0, 1, 2, 3
#: message types, in the order of the program's per-type counts
MESSAGES = ("NOP", "REQ_READ_SHARED", "REQ_READ_EXCL", "REQ_UPGRADE",
            "VOL_DOWNGRADE_S", "VOL_DOWNGRADE_I", "HOME_DOWNGRADE_S",
            "HOME_DOWNGRADE_I", "RESP_DATA", "RESP_DATA_DIRTY", "RESP_ACK",
            "RESP_NACK", "IO_READ", "IO_WRITE", "BARRIER", "IPI")
MSG = {name: i for i, name in enumerate(MESSAGES)}


class Line:
    """One line: the home's state, its buffer, the at-rest data, and each
    caching remote's state and copy (absent = I)."""

    __slots__ = ("home", "home_buf", "backing", "remote", "cache")

    def __init__(self):
        self.home = H_I
        self.home_buf: Optional[float] = None
        self.backing = 0.0
        self.remote: Dict[int, int] = {}
        self.cache: Dict[int, float] = {}


class Directory:
    """Atomic MOESI directory over ``n_remotes`` agents."""

    def __init__(self, n_remotes: int):
        self.n_remotes = n_remotes
        self.lines: Dict[int, Line] = {}
        self.counts = [0] * len(MESSAGES)

    def _line(self, line: int) -> Line:
        if line not in self.lines:
            self.lines[line] = Line()
        return self.lines[line]

    def _send(self, name: str) -> None:
        self.counts[MSG[name]] += 1

    def _drop(self, ln: Line, node: int) -> None:
        ln.remote.pop(node, None)
        ln.cache.pop(node, None)

    def _recall_owner(self, ln: Line, to_shared: bool) -> None:
        owner = [n for n, s in ln.remote.items() if s in (R_E, R_M)]
        if not owner:
            return
        o = owner[0]
        self._send("HOME_DOWNGRADE_S" if to_shared else "HOME_DOWNGRADE_I")
        if ln.remote[o] == R_M:
            self._send("RESP_DATA_DIRTY")
            if to_shared:           # MOESI: the dirty copy stays shared
                ln.home_buf = ln.cache[o]
                ln.home = H_O
            else:
                ln.backing = ln.cache[o]
        else:
            self._send("RESP_ACK")
        if to_shared:
            ln.remote[o] = R_S
        else:
            self._drop(ln, o)

    def load(self, node: int, line: int) -> None:
        ln = self._line(line)
        if ln.remote.get(node, R_I) != R_I:
            return                  # hit
        self._send("REQ_READ_SHARED")
        self._recall_owner(ln, to_shared=True)
        value = ln.home_buf if ln.home != H_I else ln.backing
        if ln.home == H_M:
            ln.home = H_O
        elif ln.home == H_E:
            ln.home = H_S
        self._send("RESP_DATA")
        ln.remote[node] = R_S
        ln.cache[node] = value

    def store(self, node: int, line: int, value: float) -> None:
        ln = self._line(line)
        state = ln.remote.get(node, R_I)
        if state in (R_E, R_M):     # silent E -> M
            ln.remote[node] = R_M
            ln.cache[node] = value
            return
        self._send("REQ_UPGRADE" if state == R_S else "REQ_READ_EXCL")
        self._recall_owner(ln, to_shared=False)
        for other in sorted(ln.remote):
            if other == node:
                continue
            self._send("HOME_DOWNGRADE_I")
            if ln.remote[other] == R_M:
                self._send("RESP_DATA_DIRTY")
                ln.backing = ln.cache[other]
            else:
                self._send("RESP_ACK")
            self._drop(ln, other)
        if ln.home in (H_M, H_O):
            ln.backing = ln.home_buf
        ln.home = H_I
        ln.home_buf = None
        self._send("RESP_ACK" if state == R_S else "RESP_DATA")
        ln.remote[node] = R_M
        ln.cache[node] = value


    def apply(self, op: int, node: int, line: int, value: float) -> None:
        """One load (op 1) or store (op 2), atomically."""
        if op == 1:
            self.load(node, line)
        elif op == 2:
            self.store(node, line, value)
        else:
            raise ValueError(f"the reference replays loads and stores, "
                             f"not op {op}")

    def holds(self, node: int, line: int) -> int:
        """The remote state ``node`` holds ``line`` in."""
        ln = self.lines.get(line)
        return R_I if ln is None else ln.remote.get(node, R_I)


def replay(n_remotes: int, ops: List[Tuple[int, int, int, float]]
           ) -> Directory:
    """Replay ``(op, remote, line, value)`` in order; op 1 = load,
    2 = store."""
    d = Directory(n_remotes)
    for op, node, line, value in ops:
        d.apply(op, node, line, value)
    return d
