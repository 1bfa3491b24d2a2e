"""The unified construction surface for streaming runs.

Before this module, a streaming experiment was assembled from 20+ loose
knobs spread across three call sites: ``EngineMN`` took 9 constructor
arguments, ``run_stream`` took 8 positional-ish kwargs, and the CLI,
smoke harness and ``bench_smoke`` each re-plumbed their own subset.  Open
-loop serving (arrival schedules + admission control) did not fit any of
them.  This module collapses the whole surface into two frozen configs:

* ``EngineConfig``  — everything that determines the ENGINE
  (remotes/lines/block/subset/credits/homes); ``.build()`` constructs
  the ``EngineMN`` (via ``EngineMN.from_config``).
* ``StreamConfig``  — everything that determines the RUN (workload,
  arrivals, admission, width, steps, observability, capture filters,
  trace collection); ``run_stream(engine, StreamConfig)`` is the single
  entry point (the legacy kwarg signature forwards here with a
  ``DeprecationWarning``, pinned bit-identical in
  ``tests/test_serving.py``).

Both serialize to/from plain JSON dicts — ``config_to_json`` /
``config_from_json`` round-trip a ``{"engine": ..., "stream": ...}``
document, which is what the CLI's ``--config`` flag consumes and what
smoke/CI write back into their artifacts bundle.  Serialization requires
the SPEC forms (``WorkloadSpec``/``ArrivalSpec`` — generator name +
seed + knobs) rather than raw arrays: a config file describes how to
regenerate the run, not a tensor dump.
"""
from __future__ import annotations

import dataclasses
import json
from typing import NamedTuple, Optional, Tuple, Union

import jax
import numpy as np

from .arrivals import ARRIVALS, ArrivalSchedule
from .observe import ObserveConfig
from .workloads import WORKLOADS, Workload

#: knob tuples are ((name, value), ...) so the dataclasses stay frozen
#: and hashable; dicts are accepted at construction via ``_params``.
Params = Tuple[Tuple[str, float], ...]


def _params(p) -> Params:
    if isinstance(p, dict):
        return tuple(sorted(p.items()))
    return tuple((k, v) for k, v in p)


@dataclasses.dataclass(frozen=True)
class WorkloadSpec:
    """Seeded recipe for a ``Workload``: generator name + stream length
    + key + generator knobs (e.g. ``store_frac``, ``alpha``)."""

    name: str = "zipfian"
    ops: int = 128
    seed: int = 0
    params: Params = ()

    def __post_init__(self):
        if self.name not in WORKLOADS:
            raise ValueError(f"unknown workload '{self.name}'; have "
                             f"{sorted(WORKLOADS)}")
        if self.ops < 1:
            raise ValueError(f"ops must be >= 1, got {self.ops}")
        object.__setattr__(self, "params", _params(self.params))

    def materialize(self, n_remotes: int, n_lines: int) -> Workload:
        return WORKLOADS[self.name](jax.random.key(self.seed), self.ops,
                                    n_remotes, n_lines,
                                    **dict(self.params))


@dataclasses.dataclass(frozen=True)
class ArrivalSpec:
    """Seeded recipe for an ``ArrivalSchedule``: process name + offered
    load (``rate`` ops/step/remote) + key + process knobs."""

    kind: str = "poisson"
    rate: float = 0.1
    seed: int = 0
    params: Params = ()

    def __post_init__(self):
        if self.kind not in ARRIVALS:
            raise ValueError(f"unknown arrival process '{self.kind}'; "
                             f"have {sorted(ARRIVALS)}")
        object.__setattr__(self, "params", _params(self.params))

    def materialize(self, ops: int, n_remotes: int) -> ArrivalSchedule:
        return ARRIVALS[self.kind](jax.random.key(self.seed), ops,
                                   n_remotes, self.rate,
                                   **dict(self.params))


class AdmissionConfig(NamedTuple):
    """Continuous-batching admission control (FIFO + reserve watermark,
    rtp-llm FIFOScheduler style) — STATIC: it keys the jitted streaming
    program alongside subset/width/homes.

    ``max_inflight`` caps transactions in flight across ALL remotes (the
    running batch / MSHR pool size; 0 = unbounded).  ``reserve`` holds
    back a watermark of that capacity from NEW admissions: arrivals are
    admitted FIFO (globally, by arrival stamp) only while
    ``inflight < max_inflight - reserve``, so already-admitted work
    always has ``reserve`` slots of headroom to make progress before the
    queue drains further — admission gates WHEN an op enters flight,
    never what it does."""

    max_inflight: int = 0
    reserve: int = 0


@dataclasses.dataclass(frozen=True, eq=False)
class EngineConfig:
    """Everything that determines the engine; ``.build()`` constructs it."""

    remotes: int = 4
    lines: int = 64
    block: int = 2
    subset: str = ""            # "" -> moesi flag picks the full protocol
    moesi: bool = True
    credits: int = 0            # uniform per-VC credit override (0 = default)
    shared_credits: bool = False
    homes: int = 1
    home_bw: int = 0
    kernel_backend: str = ""    # ""/"xla"/"pallas"; "" -> env -> "xla"
    packed: bool = False        # bit-packed directory/MSHR word planes

    def __post_init__(self):
        from ..core.engine_mn import KERNEL_BACKENDS, MAX_REMOTES
        if self.kernel_backend and \
                self.kernel_backend not in KERNEL_BACKENDS:
            raise ValueError(
                f"kernel_backend must be '' or one of {KERNEL_BACKENDS}, "
                f"got '{self.kernel_backend}'")
        if not 1 <= self.remotes <= MAX_REMOTES:
            raise ValueError(f"remotes must be in 1..{MAX_REMOTES} "
                             f"(EWF v2 node-id field), got {self.remotes}")
        if self.subset:
            from ..core.protocol import SUBSETS
            if self.subset not in SUBSETS:
                raise ValueError(f"unknown subset '{self.subset}'; have "
                                 f"{sorted(SUBSETS)}")
        if self.homes < 1 or self.lines % self.homes:
            raise ValueError(
                f"homes ({self.homes}) must be >= 1 and divide lines "
                f"({self.lines}) — address interleaving shards the line "
                f"space evenly")
        if self.credits < 0 or self.home_bw < 0:
            raise ValueError("credits and home_bw must be >= 0")

    def build(self):
        from ..core.engine_mn import EngineMN
        return EngineMN.from_config(self)

    def to_json_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True, eq=False)
class StreamConfig:
    """Everything that determines one streaming run.

    ``workload`` (and ``arrivals``) may be either concrete arrays
    (``Workload`` / ``ArrivalSchedule`` — programmatic use) or seeded
    specs (``WorkloadSpec`` / ``ArrivalSpec`` — the JSON-serializable
    form the CLI and CI drive).  ``steps=0`` auto-derives the budget via
    ``driver.default_steps`` (arrival-aware: the budget covers the last
    arrival plus the closed-loop drain tail)."""

    workload: Union[Workload, WorkloadSpec] = \
        dataclasses.field(default_factory=WorkloadSpec)
    arrivals: Optional[Union[ArrivalSchedule, ArrivalSpec]] = None
    admission: Optional[AdmissionConfig] = None
    width: int = 1
    steps: int = 0
    observe: Optional[ObserveConfig] = None
    line_filter: Optional[np.ndarray] = None
    type_filter: Optional[np.ndarray] = None
    collect_trace: bool = False

    def __post_init__(self):
        if self.width < 1:
            raise ValueError(f"width must be >= 1, got {self.width}")
        if self.steps < 0:
            raise ValueError(f"steps must be >= 0 (0 = auto), "
                             f"got {self.steps}")
        if self.admission is not None:
            adm = AdmissionConfig(*self.admission)
            if adm.max_inflight < 0 or adm.reserve < 0 or (
                    adm.max_inflight and
                    adm.reserve >= adm.max_inflight):
                raise ValueError(
                    f"admission reserve ({adm.reserve}) must leave room "
                    f"under max_inflight ({adm.max_inflight})")
            object.__setattr__(self, "admission", adm)

    # -- JSON round-trip ---------------------------------------------------

    def to_json_dict(self) -> dict:
        if not isinstance(self.workload, WorkloadSpec):
            raise ValueError(
                "StreamConfig JSON serialization requires a WorkloadSpec "
                "(generator name + seed), not raw Workload arrays")
        if self.arrivals is not None and \
                not isinstance(self.arrivals, ArrivalSpec):
            raise ValueError(
                "StreamConfig JSON serialization requires an ArrivalSpec "
                "(process name + rate + seed), not a raw schedule")
        if self.line_filter is not None or self.type_filter is not None:
            raise ValueError("capture filters are arrays and do not "
                             "serialize; set them programmatically")
        d = {
            "workload": dataclasses.asdict(self.workload),
            "arrivals": (None if self.arrivals is None
                         else dataclasses.asdict(self.arrivals)),
            "admission": (None if self.admission is None
                          else dict(self.admission._asdict())),
            "width": self.width,
            "steps": self.steps,
            "collect_trace": self.collect_trace,
        }
        if self.observe is not None:
            obs = dict(self.observe._asdict())
            obs["specs"] = list(obs["specs"])
            d["observe"] = obs
        return d


@dataclasses.dataclass(frozen=True, eq=False)
class FleetConfig:
    """One compiled program for a whole sweep (``traffic.fleet``).

    ``members`` is the sweep's point list — ``(EngineConfig,
    StreamConfig)`` pairs, one per sweep point — and ``run_fleet`` vmaps
    ONE streaming program over all of them: members may differ in
    remotes, width, workload, homes and home_bw (those become traced
    per-member data — padded workload columns, a traced width cap, the
    engine's ``home_group``/``home_bw_t`` emulation operands), so an
    R x W grid or an H in {1,2,4} sweep compiles ONCE instead of once
    per point.  Every per-member result is BIT-identical to running that
    member solo (``tests/test_fleet.py``), provided the solo run uses
    the fleet's shared ``steps`` budget.

    What must stay uniform is exactly what the traced program cannot
    batch over: shapes (``lines``/``block``) and static program
    structure (``subset``/``moesi``/``credits``/``kernel_backend``,
    ``collect_trace``).  Open-loop members (arrivals/admission),
    observability and capture filters are out of scope — those key the
    program per member, which is the per-point compile the fleet exists
    to amortize.

    ``homes > 1`` members ride on the flat-layout emulation, which is
    exact only while VC credits never bind (the folded engine splits
    credit parity by plane-local line index): effective credits
    (``credits`` or the transport default) must cover ``lines``.

    ``steps = 0`` auto-derives the shared budget as the max of the
    members' ``driver.default_steps`` — every member retires within it.

    ``mesh_devices > 0`` runs the fleet data-parallel over that many
    devices (``shard_map`` over a 1-D "fleet" mesh) — the chips of a TPU
    host, or on the CPU host devices exposed with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (what CI's
    multi-device smoke job does).  Members are independent, so
    per-member results stay bit-identical to the single-device fleet —
    and to solo runs.  The member axis pads to a device multiple by
    repeating members (their results are dropped on readout, like the
    NOP remote columns).
    """

    members: Tuple[Tuple[EngineConfig, StreamConfig], ...] = ()
    steps: int = 0
    mesh_devices: int = 0

    def __post_init__(self):
        object.__setattr__(self, "members", tuple(
            (e, s) for e, s in self.members))
        if not self.members:
            raise ValueError("FleetConfig needs at least one member")
        if self.steps < 0:
            raise ValueError(f"steps must be >= 0 (0 = auto), "
                             f"got {self.steps}")
        if self.mesh_devices < 0:
            raise ValueError(f"mesh_devices must be >= 0 (0 = single "
                             f"device), got {self.mesh_devices}")
        e0, s0 = self.members[0]
        for i, (e, s) in enumerate(self.members):
            for f in ("lines", "block", "subset", "moesi", "credits",
                      "kernel_backend", "packed"):
                if getattr(e, f) != getattr(e0, f):
                    raise ValueError(
                        f"fleet member {i}: '{f}' must be uniform across "
                        f"the fleet ({getattr(e, f)!r} != "
                        f"{getattr(e0, f)!r}) — it shapes the one traced "
                        f"program")
            if e.shared_credits:
                raise ValueError(
                    f"fleet member {i}: shared_credits is not supported "
                    f"in fleets (its credit ranking is order-sensitive "
                    f"across the whole [R, L] slab)")
            if e.homes > 1 and (e.credits or 64) < e.lines:
                raise ValueError(
                    f"fleet member {i}: homes={e.homes} requires "
                    f"effective credits >= lines ({e.lines}) — the flat "
                    f"H-emulation is exact only while credits never bind")
            if not isinstance(s.workload, WorkloadSpec):
                raise ValueError(
                    f"fleet member {i}: fleet members need a seeded "
                    f"WorkloadSpec (regenerated at the member's own "
                    f"[R, L]), not raw Workload arrays")
            if s.workload.ops != s0.workload.ops:
                raise ValueError(
                    f"fleet member {i}: workload ops must be uniform "
                    f"({s.workload.ops} != {s0.workload.ops}) — the "
                    f"fleet shares one [T, R] stream plane (a shorter "
                    f"member would pad with NOPs that dilute its "
                    f"active-step accounting)")
            if s.arrivals is not None or (
                    s.admission is not None and s.admission.max_inflight):
                raise ValueError(
                    f"fleet member {i}: open-loop members (arrivals/"
                    f"admission) are not fleet-batchable")
            if s.observe is not None or s.line_filter is not None or \
                    s.type_filter is not None:
                raise ValueError(
                    f"fleet member {i}: observability/capture filters "
                    f"key the program per member and cannot ride a "
                    f"fleet")
            if s.steps:
                raise ValueError(
                    f"fleet member {i}: per-member steps must be 0 — the "
                    f"fleet runs ONE shared budget (FleetConfig.steps)")
            if s.collect_trace != s0.collect_trace:
                raise ValueError(
                    f"fleet member {i}: collect_trace must be uniform")


def _check_keys(d: dict, allowed, what: str) -> None:
    unknown = sorted(set(d) - set(allowed))
    if unknown:
        raise ValueError(f"unknown {what} config keys {unknown}; "
                         f"allowed: {sorted(allowed)}")


def engine_config_from_dict(d: dict) -> EngineConfig:
    fields = {f.name for f in dataclasses.fields(EngineConfig)}
    _check_keys(d, fields, "engine")
    return EngineConfig(**d)


def stream_config_from_dict(d: dict) -> StreamConfig:
    allowed = {"workload", "arrivals", "admission", "width", "steps",
               "observe", "collect_trace"}
    _check_keys(d, allowed, "stream")
    d = dict(d)
    wl = d.get("workload", {})
    d["workload"] = WorkloadSpec(**{**wl, "params": _params(
        wl.get("params", ()))})
    arr = d.get("arrivals")
    if arr is not None:
        d["arrivals"] = ArrivalSpec(**{**arr, "params": _params(
            arr.get("params", ()))})
    adm = d.get("admission")
    if adm is not None:
        d["admission"] = AdmissionConfig(**adm)
    obs = d.get("observe")
    if obs is not None:
        obs = dict(obs)
        for key in ("specs", "inject"):
            if obs.get(key) is not None:
                obs[key] = tuple(obs[key])
        d["observe"] = ObserveConfig(**obs)
    return StreamConfig(**d)


def config_to_json(engine: EngineConfig, stream: StreamConfig) -> str:
    """The ``--config`` document: one JSON object holding both configs."""
    return json.dumps({"engine": engine.to_json_dict(),
                       "stream": stream.to_json_dict()},
                      indent=1, sort_keys=True)


def config_from_json(text: str) -> Tuple[EngineConfig, StreamConfig]:
    doc = json.loads(text)
    _check_keys(doc, ("engine", "stream"), "top-level")
    return (engine_config_from_dict(doc.get("engine", {})),
            stream_config_from_dict(doc.get("stream", {})))
