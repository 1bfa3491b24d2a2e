"""The harness on the CPU: it finds every cell's files by name, the
benchmark file keeps to its rules, the generator is seeded, the metric
arithmetic is right, and ``run.py`` refuses to report without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import harness  # noqa: E402
import trace_reduce  # noqa: E402
import traffic_gen  # noqa: E402

BENCH = json.loads(harness.BENCHMARK.read_text())
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_benchmark_file_keeps_its_rules():
    assert sorted(BENCH) == sorted(["command", "paths", "run_seconds",
                                    "configs", "workloads", "end_to_end",
                                    "per_layer"])
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert len(names) == len(set(names))
    assert all(harness.NAME.match(n) for n in names)
    for w in BENCH["workloads"]:
        assert harness.NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in (
            "lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_its_files_by_name(cell):
    c = harness.load_cell(cell)
    assert c.config["engine"]["remotes"] >= 1
    ref = harness.load_module("references", c.config["reference"])
    assert callable(ref.replay)
    for m in c.per_layer:
        assert callable(harness.load_module("metrics", m["name"]).read)
    assert c.per_layer and len(c.end_to_end) >= 2


def test_config_keeps_its_source_numbers():
    """Nothing of the deployment is cut: 131,072 lines of 32 float32
    words (a 16 MB L2 of 128-byte lines); the agent count and the agents'
    cache capacity are not ECI's, and say so under ``assumed``."""
    for c in BENCH["configs"]:
        cfg = json.loads((harness.CHECKOUT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"] == []
        eng = cfg["engine"]
        assert eng["lines"] * eng["block"] * 4 == 16 * 2 ** 20
        assert {"remotes", "capacity"} <= set(cfg["assumed"])
        assert "not ECI" in cfg["assumed"]["remotes"] or \
            "not a published" in cfg["assumed"]["remotes"]


def test_a_new_cell_is_found_by_name():
    """A cell added as an entry (here only in memory) resolves through
    the same files; a metric narrowed to other cells is left out."""
    bench = json.loads(json.dumps(BENCH))
    first = bench["workloads"][0]
    bench["workloads"].append(dict(first, name="added_cell"))
    bench["per_layer"].append(dict(bench["per_layer"][0], name="only_there",
                                   workloads=[first["name"]]))
    c = harness.load_cell("added_cell", bench)
    assert c.traffic == harness.load_cell(first["name"]).traffic
    assert "only_there" not in [m["name"] for m in c.per_layer]
    with pytest.raises(KeyError):
        harness.load_cell("no_such_cell", bench)
    with pytest.raises(FileNotFoundError):
        harness.load_module("metrics", "no_such_metric")
    with pytest.raises(ValueError):
        harness.load_module("metrics", "../run")


@pytest.mark.parametrize("traffic", sorted(os.listdir(
    os.path.join(HERE, "traffic"))))
def test_generator_is_seeded(traffic):
    t = json.loads(open(os.path.join(HERE, "traffic", traffic)).read())
    R, L = 48, 131072
    seed = 2 ** 40 + 3
    a = traffic_gen.generate(t, R, L, seed, 0)
    b = traffic_gen.generate(t, R, L, seed, 0)
    c = traffic_gen.generate(t, R, L, seed, 1)
    for x, y in zip(a[:3], b[:3]):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a.line, c.line)
    T = t["ops_per_remote"]
    assert a.op.shape == (T, R) and set(np.unique(a.op)) <= {1, 2}
    assert a.line.min() >= 0 and a.line.max() < L
    # distinct store values that no narrower float holds
    assert len(np.unique(a.value)) == a.value.size
    assert (a.value.view(np.uint32) & 1).all()
    if a.arrival is not None:
        np.testing.assert_array_equal(a.arrival, b.arrival)
        # every point of every seed ends its arrivals on the same step
        assert a.arrival.max() == c.arrival.max()


@pytest.mark.parametrize("kind", sorted(
    f[:-3] for f in os.listdir(os.path.join(HERE, "patterns"))
    if f.endswith(".py")))
def test_every_pattern_is_seeded(kind):
    t = {"workload": {"kind": kind}, "ops_per_remote": 4}
    a, b = (traffic_gen.generate(t, 8, 256, 5, 3) for _ in range(2))
    np.testing.assert_array_equal(a.op, b.op)
    np.testing.assert_array_equal(a.line, b.line)
    assert a.line.shape == (4, 8) and 0 <= a.line.min() <= a.line.max() < 256


def test_a_new_pattern_is_found_by_name():
    """A traffic file names its pattern and arrivals; the generator finds
    each as a file of its own, and refuses one that is not there."""
    t = {"workload": {"kind": "zipfian", "alpha": 0.9}, "ops_per_remote": 2,
         "arrivals": {"kind": "no_such_arrivals"}}
    with pytest.raises(FileNotFoundError):
        traffic_gen.generate(t, 4, 64, 1, 0)
    with pytest.raises(FileNotFoundError):
        traffic_gen.generate(dict(t, workload={"kind": "no_such"},
                                  arrivals=None), 4, 64, 1, 0)


def test_poisson_window_offers_its_rate():
    arrivals = harness.load_module("arrivals", "poisson_window")
    rate, T, R = 0.05, 16, 64
    steps = np.concatenate([arrivals.generate(
        traffic_gen.point_rng(s, 0), T, R, rate) for s in range(20)], 1)
    window = arrivals.window(T, rate)
    assert steps.max() == window - 1 and steps.min() >= 0
    # T arrivals per remote over the window: the offered rate
    assert T / window == pytest.approx(rate, rel=0.01)
    assert steps.mean() == pytest.approx((window - 1) / 2, rel=0.05)


def _member(retired, steps, active):
    return harness.Member(None, True, retired, steps, active, None, None,
                          None, None)


def _points(retired, steps, active):
    return [harness.Point([_member(r, s, a)])
            for r, s, a in zip(retired, steps, active)]


def test_metric_arithmetic():
    win = harness.Window(_points([96, 96, 96], [280] * 3, [70, 56, 42]),
                         start=10.0, end=64.0, compiles=0, trace=None)
    cell = harness.load_cell(CELLS[0])
    e2e = harness.end_to_end(cell, win, setup_s=31.5, peak_bytes=5.2e9)
    # a rate over the whole window, not a mean of per-point rates
    assert e2e["sim_ops_per_s"]["value"] == pytest.approx(288 / 54)
    assert e2e["peak_hbm_gb"]["value"] == pytest.approx(5.2)
    assert e2e["setup_s"]["value"] == 31.5
    summary = trace_reduce.Summary(
        window_s=50.0, busy_s=49.0, stream_module="jit_run",
        stream_device_s=42.0, point_spans=[(18.0, 17.5), (17.0, 16.0)],
        device_ops=[], idle_gaps=[])
    data = harness.RunData(win.points, 54.0, summary)
    read = lambda name: harness.load_module("metrics", name).read(data)
    assert read("active_step_share") == pytest.approx(100 * 168 / 840)
    assert read("step_device_ms") == pytest.approx(1e3 * 42.0 / 840)
    assert read("host_ms_per_point") == pytest.approx(1e3 * 1.5 / 2)
    assert read("device_idle_share") == pytest.approx(2.0)
    blank = harness.RunData(win.points, 54.0, None)
    for name in ("step_device_ms", "host_ms_per_point",
                 "device_idle_share"):
        assert harness.load_module("metrics", name).read(blank) is None


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, script, "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    out = _run(str(harness.CHECKOUT), "benchmarks/chip/run.py")
    assert out.returncode != 0
    assert '"correct"' not in out.stdout


def test_run_refuses_with_only_the_benchmark(tmp_path):
    """In a directory that holds only BENCHMARK.json and the benchmark's
    own files, it exits non-zero and prints no result."""
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(harness.BENCHMARK, tmp_path / "BENCHMARK.json")
    out = _run(str(tmp_path), "benchmarks/chip/run.py")
    assert out.returncode != 0
    assert '"correct"' not in out.stdout


@pytest.mark.parametrize("env", [None, "given"])
def test_compile_cache_dir(monkeypatch, tmp_path, env):
    """The cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
    the fixed directory inside the checkout."""
    import jax
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        harness.enable_compile_cache()
        want = str(harness.CACHE_DIR) if env is None else str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)
