"""The per-phase reduction on a synthetic trace and HLO text whose
answers are known, and on a trace the profiler records here."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import phase_reduce as pr  # noqa: E402
import trace_reduce as tr  # noqa: E402
from trace_reduce import Event as E  # noqa: E402
from trace_reduce import Line, Plane  # noqa: E402

BODY = "jit(run)/while/body/closed_call"
HLO = f"""HloModule jit_run

%fused_computation.1 (p: s32[4]) -> s32[4] {{
  %p = s32[4]{{0}} parameter(0)
  ROOT %add.1 = s32[4]{{0}} add(%p, %p), metadata={{op_name="{BODY}/eci.step/eci.directory/eci.transport/eci.credit_rank/add"}}
}}

%fused_computation.2 (q: s32[4]) -> s32[4] {{
  %q = s32[4]{{0}} parameter(0)
  ROOT %scatter.1 = s32[4]{{0}} scatter(%q, %q, %q), to_apply=%region.1
}}

%body (c: (s32[4], s32[9])) -> (s32[4], s32[9]) {{
  %c = (s32[4]{{0}}, s32[9]{{0}}) parameter(0)
  %gte.1 = s32[4]{{0}} get-tuple-element(%c), index=0
  %gte.2 = s32[9]{{0}} get-tuple-element(%c), index=1
  %fusion.1 = s32[4]{{0}} fusion(%gte.1), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{BODY}/eci.step/eci.directory/eci.transport/eci.credit_rank/add"}}
  %fusion.2 = s32[4]{{0}} fusion(%fusion.1), kind=kCustom, calls=%fused_computation.2
  %reshape.1 = s32[2,2]{{1,0}} reshape(%fusion.2), metadata={{op_name="{BODY}/eci.retire/scatter"}}
  %copy-start.1 = (s32[9]{{0}}, s32[9]{{0}}, u32[]) copy-start(%gte.2)
  %copy-done.1 = s32[9]{{0}} copy-done(%copy-start.1)
  %fusion.3 = s32[4]{{0}} fusion(%copy-done.1, %fusion.1), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{BODY}/eci.counters/reduce_sum"}}
  %copy.1 = s32[4]{{0}} copy(%fusion.3), metadata={{op_name="{BODY}"}}
  ROOT %tuple.1 = (s32[4]{{0}}, s32[9]{{0}}) tuple(%copy.1, %copy-done.1)
}}
"""


def test_an_op_takes_its_innermost_phase():
    ph = pr.hlo_phases(HLO)
    # the fan-out's ranking: credit_rank, not directory or transport
    assert ph["fusion.1"] == "eci.credit_rank"
    assert ph["fusion.3"] == "eci.counters"
    assert pr.innermost(f"{BODY}/eci.agents/eci.credit_rank/cumsum") == \
        "eci.credit_rank"
    assert pr.innermost(f"{BODY}/eci.step") == "eci.step"


def test_an_op_under_no_scope_is_unphased():
    ph = pr.hlo_phases(HLO)
    assert ph["copy.1"] is None
    assert pr.innermost(BODY) is None


def test_an_op_without_metadata_takes_its_consumers_phase():
    """A compiler-made scatter fusion and an asynchronous copy carry no
    ``op_name``: each takes the phase of what consumes its result."""
    ph = pr.hlo_phases(HLO)
    assert ph["fusion.2"] == "eci.retire"
    assert ph["copy-start.1"] == "eci.counters"


def op(name, start, dur):
    return E(f"%{name} = s32[4]{{0}} fusion(%a)", start, dur)


def synthetic(devices=1, window=True):
    host = [E("bench.generate", 50, 50),
            E("bench.run_stream", 100, 550),
            E("eci.prepare", 100, 10),
            E("eci.dispatch", 110, 15),
            E("eci.readback", 125, 515),
            E("bench.extract", 650, 40)]
    if window:
        host.insert(0, E("bench.window", 0, 1000))
    dev = [Plane(f"/device:TPU:{i}", [
        Line("XLA Modules", [E("jit_run", 120, 280),
                             E("jit_small", 700, 20)]),
        Line("XLA Ops", [op("fusion.1", 120, 80), op("fusion.2", 200, 60),
                         op("copy.1", 260, 20), op("fusion.3", 330, 50),
                         op("fusion.9", 700, 20)]),
        Line("Async XLA Ops", [E("%copy-start.1 = (s32[9]{0}) copy-start()",
                                 270, 70)]),
    ]) for i in range(devices)]
    return [Plane("/host:CPU", [Line("python", host)])] + dev


PHASE_OF = pr.hlo_phases(HLO)


def test_phase_split_and_its_identity():
    s = pr.summarize(synthetic(), PHASE_OF)
    assert s.stream_module == "jit_run"
    assert s.phase_s == {"eci.credit_rank": pytest.approx(80e-9),
                         "eci.retire": pytest.approx(60e-9),
                         "eci.counters": pytest.approx(50e-9)}
    assert s.unphased_s == pytest.approx(20e-9)
    # [280, 330] and [380, 400] inside the module run no op
    assert s.scan_wait_s == pytest.approx(70e-9)
    ident = pr.identity(s, steps=2)
    assert ident["parts_ms"] == pytest.approx(ident["step_device_ms"])
    assert ident["gap"] == pytest.approx(0, abs=1e-12)
    m = pr.metrics(s, steps=2)
    assert m["credit_rank_ms_per_step"] == pytest.approx(1e3 * 40e-9)
    assert m["retire_ms_per_step"] == pytest.approx(1e3 * 30e-9)
    assert m["counters_ms_per_step"] == pytest.approx(1e3 * 25e-9)
    assert m["agents_ms_per_step"] == 0.0
    assert m["unphased_device_share"] == pytest.approx(100 * 20 / 210)
    assert m["scan_wait_ms_per_step"] == pytest.approx(1e3 * 35e-9)
    # per step: phases + unphased + waits = the stream module's time
    unphased_ms = m["unphased_device_share"] / 100 * (
        1e3 * s.stream_device_s / 2 - m["scan_wait_ms_per_step"])
    assert sum(m[k] for k in pr.PHASE_METRICS) + unphased_ms + \
        m["scan_wait_ms_per_step"] == pytest.approx(
            1e3 * s.stream_device_s / 2)


def test_idle_gap_under_an_async_copy_is_put_down_to_its_phase():
    s = pr.summarize(synthetic(), PHASE_OF)
    assert s.idle_phases["async eci.counters"] == pytest.approx(50e-9)


def test_host_gap_is_named_by_the_program_span_over_its_middle():
    s = pr.summarize(synthetic(), PHASE_OF)
    # [380, 700], from the module's last op past its end, while the host
    # was in eci.readback; [0, 120] while it generated inputs; [720,
    # 1000] in the window alone
    assert s.idle_phases == {
        "eci.readback": pytest.approx(320e-9),
        "bench.window": pytest.approx(280e-9),
        "bench.generate": pytest.approx(120e-9),
        "async eci.counters": pytest.approx(50e-9)}
    # trace_reduce, unchanged, names the same gap by the program span
    assert ["bench.run_stream/eci.readback", pytest.approx(320e-9)] in \
        tr.summarize(synthetic()).idle_gaps


def test_program_host_spans_per_point():
    s = pr.summarize(synthetic(), PHASE_OF)
    (p,) = s.point_host
    assert p["eci.prepare"] == pytest.approx(10e-9)
    assert p["eci.dispatch"] == pytest.approx(15e-9)
    assert p["eci.readback"] == pytest.approx(515e-9)
    # [125, 640] less the stream module's [120, 400]
    assert p["readback_uncovered"] == pytest.approx(240e-9)
    m = pr.metrics(s, steps=2)
    assert m["stream_host_ms_per_point"] == pytest.approx(1e3 * 265e-9)


def test_devices_are_averaged():
    one = pr.summarize(synthetic(1), PHASE_OF)
    two = pr.summarize(synthetic(2), PHASE_OF)
    assert two.phase_s == pytest.approx(one.phase_s)
    assert two.scan_wait_s == pytest.approx(one.scan_wait_s)
    assert two.idle_phases == pytest.approx(one.idle_phases)
    assert two.point_host == [pytest.approx(one.point_host[0])]


def test_nothing_to_read_without_a_window():
    planes = synthetic(window=False)
    assert pr.summarize(planes, PHASE_OF) is None
    assert pr.metrics(pr.summarize(planes, PHASE_OF), steps=2) is None
    assert pr.metrics(pr.summarize(synthetic(), PHASE_OF), steps=0) is None
    assert pr.summarize(synthetic()[:1], PHASE_OF) is None   # no device


def test_breakdown_keys():
    b = pr.breakdown(pr.summarize(synthetic(), PHASE_OF))
    assert [k for k, _ in b["phases"]] == [
        "eci.credit_rank", "eci.retire", "eci.counters", "unphased",
        "scan_wait"]
    assert dict(b["idle_phases"])["async eci.counters"] == \
        pytest.approx(50e-9)


def test_reads_the_program_spans_of_a_recorded_trace(tmp_path):
    """On the CPU a recorded trace holds the window, the point and the
    program's three host spans in order inside it, but no device plane,
    so there is no phase split to read."""
    import jax
    from jax.profiler import TraceAnnotation
    from repro.traffic import EngineConfig, StreamConfig, WorkloadSpec
    from repro.traffic import run_stream
    eng = EngineConfig(remotes=2, lines=16).build()
    cfg = StreamConfig(WorkloadSpec("zipfian", ops=4, seed=2))
    run_stream(eng, cfg)                          # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation(tr.WINDOW_SPAN):
            with TraceAnnotation(tr.POINT_SPAN):
                assert run_stream(eng, cfg).completed
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    planes = tr.load(str(path))
    host = tr.bench_thread(planes)
    (win,) = [e for e in host if e.name == tr.WINDOW_SPAN]
    (spans,) = pr.host_spans(host, win.start, win.end)
    ordered = sorted(spans.values(), key=lambda e: e.start)
    assert [e.name for e in ordered] == list(pr.HOST_SPANS)
    assert all(a.end <= b.start for a, b in zip(ordered, ordered[1:]))
    assert tr.summarize(planes) is None
    assert pr.summarize(planes, {}) is None


def test_phase_profile_runs_a_small_cell_here():
    """The profiling run end to end at R=4, L=64 on the CPU: points,
    steps and the program-counter metric, but no device phases."""
    import time

    import harness
    import phase_profile
    c = harness.load_cell("enzian_zipf_poisson")
    engine = dict(c.config["engine"], remotes=4, lines=64, block=2)
    cell = c._replace(config=dict(c.config, engine=engine))
    out = phase_profile.profile(cell, 2 ** 40 + 7, 0.5, time.perf_counter())
    assert out["points"] >= 1 and out["compiles_in_window"] == 0
    assert out["steps"] > 0 and out["steps"] % out["points"] == 0
    assert set(out["metrics"]) == {"active_step_share"}
    assert "breakdown" not in out


def test_phase_profile_refuses_without_a_tpu():
    import subprocess
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, os.path.join(here, "phase_profile.py"),
         "--workload", "enzian_zipf_closed", "--seed", "1", "--seconds",
         "1"], env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and out.stdout == ""
