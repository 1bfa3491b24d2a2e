"""The fleet path of the harness on the CPU: a configuration with a
``"fleet"`` group runs as one ``run_fleet`` call per point, sharded over
the cell's chips, each member draws inputs of its own, each member is
bit-identical to its solo run, the metrics read a fleet point as one
call, and the one-engine cells' inputs are the ones they always had.
The fleet cells are ``test_control.FLEETS``: no cell of the benchmark
takes the fleet path yet."""
import hashlib
import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import fleet_solo  # noqa: E402
import harness  # noqa: E402
import test_control  # noqa: E402
import traffic_gen  # noqa: E402

BENCH = json.loads(harness.BENCHMARK.read_text())
FLEETS = test_control.FLEETS
SEED = 2 ** 33 + 5

#: sha256 of the inputs of points -1, 0 and 1 at R=48, L=131,072, seed
#: 2**40 + 3, as the generator made them before fleets had generators of
#: their own.
INPUT_HASHES = {
    "zipf_closed":
        "2de1569cdcfadcddf1786b71c74072fd6a1927945e779b0730277f2dcbb60bf5",
    "zipf_poisson":
        "160ee3773f1504eb0cfe9e4f856a102119e29b54b307504b1a4990257c0b284f",
}


def test_a_fleet_cell_asks_for_its_chips():
    """One member per chip of the cell; on one chip, all on it.  No
    configuration names a mesh of its own, which could disagree."""
    assert FLEETS
    cell = test_control.tiny_cell(FLEETS[0])
    assert harness.make_target(cell).mesh_devices == 0
    assert harness.make_target(cell._replace(chips=4)).mesh_devices == 4
    for w in BENCH["workloads"]:
        cfg = harness.load_cell(w["name"]).config
        assert "mesh_devices" not in cfg.get("fleet", {})


@pytest.mark.parametrize("traffic", sorted(INPUT_HASHES))
def test_one_engine_inputs_are_kept(traffic):
    t = json.loads(open(os.path.join(HERE, "traffic",
                                     traffic + ".json")).read())
    h = hashlib.sha256()
    for point in (-1, 0, 1):
        for a in traffic_gen.generate(t, 48, 131072, 2 ** 40 + 3, point):
            if a is not None:
                h.update(a.tobytes())
    assert h.hexdigest() == INPUT_HASHES[traffic]


@pytest.mark.parametrize("name", FLEETS)
def test_members_draw_inputs_of_their_own(name):
    cell = test_control.tiny_cell(name)
    target = harness.make_target(cell)
    assert isinstance(target, harness.FleetTarget)
    a, b = target.inputs(SEED, 0), target.inputs(SEED, 0)
    c = target.inputs(SEED, 1)
    T = cell.traffic["ops_per_remote"]
    remotes = cell.config["fleet"]["remotes"]
    assert [x.op.shape for x in a] == [(T, r) for r in remotes]
    for x, y, z in zip(a, b, c):
        for u, v in zip(x[:3], y[:3]):
            np.testing.assert_array_equal(u, v)
        assert not np.array_equal(x.value, z.value)
    values = [x.value.ravel() for x in a]
    for i in range(len(values)):
        for j in range(i):
            assert not np.intersect1d(values[i], values[j]).size
    # member 0 is not the one-engine point of the same seed
    solo = traffic_gen.generate(cell.traffic, remotes[0],
                                cell.config["engine"]["lines"], SEED, 0)
    assert not np.array_equal(solo.value, a[0].value)


def test_fleet_config_is_checked():
    cell = test_control.tiny_cell(FLEETS[0])
    bad = dict(cell.config, engine=dict(cell.config["engine"], remotes=8))
    with pytest.raises(ValueError, match="largest"):
        harness.make_target(cell._replace(config=bad))
    open_loop = harness.load_cell(next(
        w["name"] for w in BENCH["workloads"]
        if harness.load_cell(w["name"]).traffic.get("arrivals"))).traffic
    target = harness.make_target(cell._replace(traffic=open_loop))
    with pytest.raises(ValueError, match="closed-loop"):
        target.run(target.inputs(SEED, 0))


@pytest.mark.parametrize("name", FLEETS)
def test_fleet_members_match_their_solo_runs(name):
    import jax
    lines = fleet_solo.compare(test_control.tiny_cell(name), SEED,
                               jax.devices())
    assert len(lines) == len(test_control.tiny_cell(name).config[
        "fleet"]["remotes"])
    for line in lines:
        assert line["bit_identical"], line["differs_in"]
        assert line["retired"] > 0


def test_fleet_metric_arithmetic():
    """A fleet point is one call: its ops are every member's, its steps
    the budget each member scans; the active share is over members."""
    def member(retired, active):
        return harness.Member(None, True, retired, 388, active, None, None,
                              None, None)
    points = [harness.Point([member(24, 100), member(144, 131)]),
              harness.Point([member(24, 90), member(144, 140)])]
    win = harness.Window(points, start=0.0, end=50.0, compiles=0,
                         trace=None)
    cell = test_control.tiny_cell(FLEETS[0])
    e2e = harness.end_to_end(cell, win, setup_s=40.0, peak_bytes=11e9)
    assert e2e["sim_ops_per_s"]["value"] == pytest.approx(336 / 50)
    summary = harness.trace_reduce.Summary(
        window_s=50.0, busy_s=45.0, stream_module="jit_run",
        stream_device_s=48.0, point_spans=[(24.5, 24.0), (24.4, 24.0)],
        device_ops=[], idle_gaps=[])
    data = harness.RunData(points, 50.0, summary)
    read = lambda n: harness.load_module("metrics", n).read(data)
    assert read("active_step_share") == pytest.approx(
        100 * 461 / (4 * 388))
    # one chip's stream time per step of one call, not the members' sum
    assert read("step_device_ms") == pytest.approx(1e3 * 48.0 / 776)
    assert read("host_ms_per_point") == pytest.approx(1e3 * 0.9 / 2)


_FOUR_DEVICES = """
    import json, os, sys, time
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = [{here!r}, {src!r}]
    import jax
    import fleet_solo, harness, test_control
    assert len(jax.devices()) == 4
    cell = test_control.tiny_cell({name!r})._replace(chips=4)
    res = harness.run_cell(cell, {seed}, 0.0, False, jax.devices(),
                           time.perf_counter())
    lines = fleet_solo.compare(cell, {seed}, jax.devices())
    print("RESULT::" + json.dumps({{
        "correct": res["correct"], "checks": res["checks"],
        "count": res["device"]["count"],
        "devices": [l["device"] for l in lines],
        "identical": [l["bit_identical"] for l in lines],
        "differs_in": [l["differs_in"] for l in lines]}}))
"""


@pytest.mark.parametrize("name", FLEETS)
def test_fleet_sharded_over_four_devices(name):
    """The cell's own path, one member per device on four forced host
    devices: correct, each member read back where it ran, and each
    bit-identical to its solo run there."""
    script = textwrap.dedent(_FOUR_DEVICES.format(
        here=HERE, src=str(harness.CHECKOUT / "src"), name=name, seed=SEED))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    line = next(x for x in out.stdout.splitlines()
                if x.startswith("RESULT::"))
    r = json.loads(line[len("RESULT::"):])
    assert r["correct"], r["checks"]
    assert r["count"] == 4 and len(set(r["devices"])) == 4
    assert all(r["identical"]), r["differs_in"]
