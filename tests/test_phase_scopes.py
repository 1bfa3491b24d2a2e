"""The stream program names its phases: ``eci.*`` scopes in the compiled
program's ``op_name`` metadata, and ``eci.*`` host spans around every
``run_stream`` and ``run_fleet`` call on the profiler's clock.

A profiler trace of the chip attributes each device op to the innermost
``eci.*`` scope of its ``op_name``; these tests pin that every scope the
program compiles is there, and only where the program has the code it
names (admission and sojourn only in the open loop, the observability
fold only with an ``ObserveConfig``).
"""
import re

import jax
import pytest

from repro.traffic import (AdmissionConfig, ArrivalSpec, EngineConfig,
                           FleetConfig, StreamConfig, WorkloadSpec,
                           run_fleet, run_stream, stream_program)
from repro.traffic.counters import N_SOJ_BUCKETS
from repro.traffic.observe import ObserveConfig

R, L = 4, 64
STEP_PHASES = {"eci.step", "eci.transport", "eci.credit_rank",
               "eci.arbitrate", "eci.directory", "eci.agents"}
SCAN_PHASES = {"eci.issue", "eci.retire", "eci.counters"} | STEP_PHASES
HOST_SPANS = ["eci.prepare", "eci.dispatch", "eci.readback"]

CONFIGS = {
    "closed": StreamConfig(WorkloadSpec("zipfian", ops=8, seed=3),
                           collect_trace=True),
    "open_admission": StreamConfig(
        WorkloadSpec("zipfian", ops=8, seed=3),
        arrivals=ArrivalSpec("poisson", rate=0.2, seed=4),
        admission=AdmissionConfig(max_inflight=8, reserve=2),
        collect_trace=True),
    "observed": StreamConfig(WorkloadSpec("zipfian", ops=8, seed=3),
                             observe=ObserveConfig()),
}


@pytest.fixture(scope="module")
def hlo():
    """Each configuration's compiled program, as HLO text."""
    eng = EngineConfig(remotes=R, lines=L).build()
    out = {}
    for name, cfg in CONFIGS.items():
        fn, operands = stream_program(eng, cfg)
        out[name] = fn.lower(*operands).compile().as_text()
    return out


@pytest.fixture(scope="module")
def op_names(hlo):
    """``op_name`` metadata of each configuration's compiled program."""
    return {name: set(re.findall(r'op_name="([^"]*)"', text))
            for name, text in hlo.items()}


def _scopes(names):
    return {p for n in names for p in n.split("/") if p.startswith("eci.")}


def _under(names, scope, op):
    """Some op named ``op`` sits inside ``scope``."""
    return any(f"{scope}/" in n and op in n.split(f"{scope}/", 1)[1]
               for n in names)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_compiled_program_carries_every_phase(op_names, name):
    scopes = _scopes(op_names[name])
    want = SCAN_PHASES | ({"eci.observe"} if name == "observed" else set())
    assert scopes == want


def _sojourn_fold(text):
    """Some reduce to the ``s32[N_SOJ_BUCKETS]`` sojourn / admission-wait
    histogram sits inside ``eci.retire``."""
    return re.search(rf'= s32\[{N_SOJ_BUCKETS}\]\S* reduce\(.*op_name='
                     r'"[^"]*eci\.retire/reduce_sum"', text) is not None


def test_admission_and_sojourn_only_in_the_open_loop(op_names, hlo):
    """The admission sort is issue work and the sojourn histograms are
    retirement work, and only the open-loop program has either."""
    for name, names in op_names.items():
        is_open = name == "open_admission"
        assert _under(names, "eci.issue", "argsort") == is_open, name
        assert _sojourn_fold(hlo[name]) == is_open, name


@pytest.mark.parametrize("name", sorted(CONFIGS))
@pytest.mark.parametrize("scope", ["eci.retire", "eci.counters"])
def test_histogram_folds_bucket_without_searchsorted(op_names, name, scope):
    """The histogram folds bucket by compares: a ``searchsorted`` is a
    per-element gather on a TPU, and under the sojourn fold it took most
    of an open-loop step."""
    assert not _under(op_names[name], scope, "searchsorted")


def test_ranking_counts_as_credit_rank_inside_the_fan_out(op_names):
    """A scope nests: the fan-out's credit ranking is named under
    ``eci.directory`` and, innermost, ``eci.credit_rank``."""
    assert any("eci.directory/eci.transport/eci.credit_rank/" in n
               for n in op_names["closed"])
    assert any("eci.agents/eci.credit_rank/" in n
               for n in op_names["closed"])


def _host_events(tmp_path, fn):
    """The host events of ``fn()`` run inside a ``test.point`` span under
    an active profiler trace."""
    from jax.profiler import ProfileData, TraceAnnotation
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("test.point"):
            fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.end_ns) for e in line.events]
            if any(n == "test.point" for n, _, _ in events):
                return events
    raise AssertionError("no host line holds the enclosing span")


def _assert_spans_in_order(events):
    """The three host spans, in order, one after the other, inside the
    enclosing span."""
    (outer,) = [e for e in events if e[0] == "test.point"]
    spans = sorted((e for e in events if e[0] in HOST_SPANS),
                   key=lambda e: e[1])
    assert [n for n, _, _ in spans] == HOST_SPANS
    assert outer[1] <= spans[0][1] and spans[-1][2] <= outer[2]
    assert all(a[2] <= b[1] for a, b in zip(spans, spans[1:]))


def test_run_stream_writes_host_spans_in_order(tmp_path):
    eng = EngineConfig(remotes=R, lines=L).build()
    run = []
    events = _host_events(tmp_path, lambda: run.append(
        run_stream(eng, CONFIGS["closed"])))
    assert run[0].completed
    _assert_spans_in_order(events)


def test_run_fleet_writes_host_spans_in_order(tmp_path):
    members = tuple((EngineConfig(remotes=r, lines=16),
                     StreamConfig(WorkloadSpec("zipfian", ops=4, seed=1)))
                    for r in (2, 4))
    runs = []
    events = _host_events(tmp_path, lambda: runs.extend(
        run_fleet(FleetConfig(members=members))))
    assert len(runs) == 2 and all(r.completed for r in runs)
    _assert_spans_in_order(events)
