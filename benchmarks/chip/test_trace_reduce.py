"""The trace reduction on a synthetic trace whose answers are known, and
its reader on a trace the profiler records here."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import trace_reduce as tr  # noqa: E402
from trace_reduce import Event as E  # noqa: E402
from trace_reduce import Line, Plane  # noqa: E402


def synthetic(devices=1):
    host = Plane("/host:CPU", [
        Line("other-thread", [E("bench.window", 0, 10)]),
        Line("python", [
            E("bench.window", 0, 1000),
            E("bench.generate", 50, 50),
            E("bench.run_stream", 100, 550),
            E("TransferFromDevice", 550, 100),
            E("bench.extract", 650, 40),
        ])])
    dev = [Plane(f"/device:TPU:{i}", [
        Line("XLA Modules", [E("jit_run", 120, 380), E("jit_small", 700, 50)]),
        Line("XLA Ops", [E("cumsum", 120, 180), E("select", 250, 250),
                         E("small", 700, 50),
                         E("outside", 1200, 100)]),
    ]) for i in range(devices)]
    return [host, Plane("/device:TPU:0 SparseCore", [])] + dev


def test_busy_union_idle_and_module_time():
    s = tr.summarize(synthetic())
    assert s.window_s == pytest.approx(1000e-9)
    # [120, 500] and [700, 750]: overlapping ops count once, the op
    # outside the window not at all.
    assert s.busy_s == pytest.approx(430e-9)
    assert s.stream_module == "jit_run"
    assert s.stream_device_s == pytest.approx(380e-9)
    assert s.point_spans == [pytest.approx((550e-9, 380e-9))]
    assert s.device_ops[0] == ["select", pytest.approx(250e-9)]
    assert [name for name, _ in s.device_ops] == ["select", "cumsum",
                                                  "small"]


def test_gaps_named_by_host_span():
    s = tr.summarize(synthetic())
    assert s.idle_gaps == [
        ["bench.window", pytest.approx(250e-9)],
        ["bench.run_stream/TransferFromDevice", pytest.approx(200e-9)],
        ["bench.generate", pytest.approx(120e-9)]]
    assert tr.breakdown(s)["idle_gaps"][0][0] == "bench.window"


def test_devices_are_averaged():
    one, two = tr.summarize(synthetic(1)), tr.summarize(synthetic(2))
    assert two.busy_s == pytest.approx(one.busy_s)
    assert two.stream_device_s == pytest.approx(one.stream_device_s)
    assert len(two.idle_gaps) == 6


def test_nothing_to_read():
    planes = synthetic()
    assert tr.summarize(planes[:1]) is None           # no device
    assert tr.summarize(planes[1:]) is None           # no window


def test_enclosing_ops_are_left_out():
    """A ``while`` op that spans its body's ops is neither busy time of
    its own nor a device op; ops are named by HLO name and result."""
    planes = synthetic()
    dev = planes[-1]
    ops = dev.lines[1]
    loop = E("%while.7 = (s32[]{:T(128)}, f32[48,8]{1,0}) while(...)",
             110, 400)
    planes[-1] = dev._replace(lines=[dev.lines[0], ops._replace(
        events=ops.events + [loop])])
    s = tr.summarize(planes)
    assert s.busy_s == pytest.approx(430e-9)
    assert all(not name.startswith("while") for name, _ in s.device_ops)
    assert tr.op_name("%fusion.12 = s32[192]{0:T(256)} fusion(%a)") == \
        "fusion.12 s32[192]"
    assert tr.op_name("%while.7 = (s32[]{:T(128)}, f32[4]) while()") == \
        "while.7 s32[]"
    assert tr.op_name("cumsum") == "cumsum"


def test_a_wait_on_an_async_op_is_idle_and_named_by_it():
    """An asynchronous copy in flight while no op runs is idle time, and
    the gap is named by the copy, not listed among the device ops."""
    planes = synthetic()
    dev = planes[-1]
    planes[-1] = dev._replace(lines=dev.lines + [
        Line("Async XLA Ops", [E("%copy-start.1 = s32[9]{0} copy-start()",
                                 500, 150)])])
    s = tr.summarize(planes)
    assert s.busy_s == pytest.approx(430e-9)
    assert all(not n.startswith("copy") for n, _ in s.device_ops)
    assert ["bench.run_stream/async copy-start.1 s32[9]",
            pytest.approx(200e-9)] in s.idle_gaps


def test_union_and_gaps():
    busy = tr.union([(5, 8), (0, 2), (1, 3), (9, 20)], 0, 10)
    assert busy == [(0, 3), (5, 8), (9, 10)]
    assert tr.gaps(busy, 0, 12) == [(3, 5), (8, 9), (10, 12)]


def test_reads_a_recorded_trace(tmp_path):
    """The reader takes what the profiler writes: on the CPU the trace
    has the window span but no device plane, so nothing is read."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    x = jnp.ones((8, 8))
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation(tr.WINDOW_SPAN):
            (x @ x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    planes = tr.load(str(path))
    assert any(e.name == tr.WINDOW_SPAN for e in tr.bench_thread(planes))
    assert tr.summarize(planes) is None
