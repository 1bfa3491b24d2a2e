"""The benchmark's traffic generator: one general generator that reads a
traffic file (``traffic/<name>.json``) and makes the inputs of one sweep
point from ``(seed, point)``.

The file names its access pattern (``workload.kind``), found by name as
``patterns/<kind>.py``, and its arrivals (``arrivals.kind``, found as
``arrivals/<kind>.py``; ``null`` is the closed loop, every op issuable at
step 0).  The patterns are numpy copies of the program's own generators
(``repro.traffic.workloads``), kept here so that a change to the program
cannot move the yardstick.  One thing differs on purpose: store values
are distinct float32 numbers in [1, 2) whose lowest mantissa bit is set,
so no narrower float (bfloat16, float16) holds any of them, and a payload
path that drops precision changes the line data the check compares.
(The program's ``_values`` are small integers that bfloat16 holds
exactly.)

An arrivals module has ``generate(rng, T, R, **params)`` returning the
``[T, R]`` arrival steps; every point of a traffic has to get the same
latest arrival, since the step budget is a shape of the program.

Everything is drawn from ``numpy.random.default_rng([seed, point])``,
which takes seeds of any size: the same seed gives the same inputs.  A
fleet's members each draw from a generator of their own,
``default_rng([seed, point, member + 1])``, so no two members of a point get
the same inputs, and a one-engine cell's inputs are those it always had.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import numpy as np

import byname

#: the program's op codes (``repro.core.protocol.LocalOp``); the harness
#: checks at start-up that the program still uses them.
NOP, LOAD, STORE = 0, 1, 2

_module = functools.lru_cache(maxsize=None)(byname.load_module)


class Inputs(NamedTuple):
    """One sweep point's generated inputs."""

    op: np.ndarray        # [T, R] int8 NOP/LOAD/STORE
    line: np.ndarray      # [T, R] int32
    value: np.ndarray     # [T, R] float32 store value (whole line)
    arrival: Optional[np.ndarray]   # [T, R] int32 arrival step, or None


def point_rng(seed: int, point: int,
              member: Optional[int] = None) -> np.random.Generator:
    """The generator of one point (of one fleet member, where ``member``
    is given); ``point`` -1 is the warm-up point."""
    key = [int(seed), int(point) + 1]
    # member + 1: a key that ends in 0 draws as if the 0 were not there,
    # so member 0 would draw the one-engine point's inputs.
    return np.random.default_rng(key if member is None
                                 else key + [int(member) + 1])


def store_values(rng, T, R) -> np.ndarray:
    """Distinct float32 values in [1, 2) with the lowest mantissa bit set
    (so bfloat16 and float16 round every one of them)."""
    mant = 2 * rng.choice(1 << 22, size=T * R, replace=False) + 1
    bits = np.uint32(127 << 23) | mant.astype(np.uint32)
    return bits.view(np.float32).reshape(T, R)


def _params(group: dict):
    params = dict(group)
    return params.pop("kind"), params


def generate(traffic: dict, n_remotes: int, n_lines: int, seed: int,
             point: int, member: Optional[int] = None) -> Inputs:
    """The inputs of point ``point`` of a run with ``seed`` (of fleet
    member ``member``, where it is given)."""
    rng = point_rng(seed, point, member)
    T = int(traffic["ops_per_remote"])
    kind, params = _params(traffic["workload"])
    op, line = _module("patterns", kind).generate(
        rng, T, n_remotes, n_lines, **params)
    value = store_values(rng, T, n_remotes)
    arrival = None
    if traffic.get("arrivals") is not None:
        kind, params = _params(traffic["arrivals"])
        arrival = np.asarray(_module("arrivals", kind).generate(
            rng, T, n_remotes, **params), np.int32)
    return Inputs(np.asarray(op, np.int8),
                  np.ascontiguousarray(line, dtype=np.int32), value, arrival)
