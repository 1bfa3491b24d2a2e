"""The correctness check fails what it must, at a size a test run holds.

Each run drives the harness past its look for a chip, at R=4 agents and
64 lines on the CPU: a sound run is correct; the control (the program's
own bfloat16 line-data path, one precision below the configuration's
float32) is not; nor is a run with the timed path broken underneath, by
each fault a cell can have.  The fleet path, which no cell of the
benchmark takes yet, runs as ``<closed cell>+fleet``: that cell's engine
swept over members of 1 to 4 agents as one ``run_fleet`` call on one
device.  A fleet's members are independent, with no exchange between
chips to leave out.
"""
import json
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import harness  # noqa: E402

SEED = 2 ** 35 + 17


def tiny_cell(name):
    base, fleet, _ = name.partition("+fleet")
    cell = harness.load_cell(base)
    config = dict(cell.config, engine=dict(cell.config["engine"], remotes=4,
                                           lines=64, block=4))
    if fleet:
        config["fleet"] = {"remotes": [1, 2, 3, 4]}
        cell = cell._replace(name=name, chips=1)
    # 4 agents hold few ops: at least 3 each, so that a point has stores.
    traffic = dict(cell.traffic,
                   ops_per_remote=max(3, cell.traffic["ops_per_remote"]))
    if traffic.get("arrivals"):
        # the cell's own rate suits 48 agents; 4 agents on 64 lines
        # drain faster, so the same window length is kept loaded.
        traffic["arrivals"] = dict(traffic["arrivals"], rate=0.05)
    return cell._replace(config=config, traffic=traffic)


def run(cell, **kw):
    import jax
    return harness.run_cell(cell, SEED, 0.0, False, jax.devices(),
                            time.perf_counter(), **kw)


@pytest.fixture
def plant(monkeypatch):
    """Replace the engine step the stream program is built from."""
    from repro.traffic import driver
    real = driver.step_mn

    def put(make):
        monkeypatch.setattr(driver, "step_mn", make(real))
        driver._jitted_stream.cache_clear()
    yield put
    driver._jitted_stream.cache_clear()


CELLS = [w["name"] for w in json.loads(harness.BENCHMARK.read_text())[
    "workloads"]]
FLEETS = [n + "+fleet" for n in CELLS
          if not harness.load_cell(n).traffic.get("arrivals")]


@pytest.mark.parametrize("name", CELLS + FLEETS)
def test_sound_run_is_correct(name):
    res = run(tiny_cell(name))
    assert res["correct"], res["checks"]
    assert res["attempted"] == 1 and res["failed"] == 0


@pytest.mark.parametrize("name", CELLS + FLEETS)
def test_control_lower_precision_fails(name):
    import jax.numpy as jnp
    cell = tiny_cell(name)
    res = run(cell, target_factory=lambda: harness.make_target(
        cell, jnp.bfloat16))
    assert not res["correct"]
    assert res["checks"]["data"]["value"] > 0
    assert res["checks"]["messages"]["value"] == 0


def _stuck(real):
    """A step that returns its state unchanged."""
    def step(base, tables, st, op, val, *a, **k):
        _, out = real(base, tables, st, op, val, *a, **k)
        return st, out._replace(accepted=out.accepted & False)
    return step


def _half(real):
    """Half of the agents' ops left out."""
    def step(base, tables, st, op, val, *a, **k):
        op = op.at[op.shape[0] // 2:].set(0)
        return real(base, tables, st, op, val, *a, **k)
    return step


def _value(real):
    """A stored value altered where the step takes it."""
    def step(base, tables, st, op, val, *a, **k):
        return real(base, tables, st, op, val * 1.0000001 + 2 ** -20,
                    *a, **k)
    return step


def _message(real):
    """A message count altered where the step makes it."""
    def step(base, tables, st, op, val, *a, **k):
        st2, out = real(base, tables, st, op, val, *a, **k)
        bump = (st.step_no == 3).astype(st2.msg_count.dtype)
        return st2._replace(msg_count=st2.msg_count.at[8].add(bump)), out
    return step


def _nack(real):
    """Spurious upgrade/NACK pairs, more than any store could have lost
    a race for."""
    def step(base, tables, st, op, val, *a, **k):
        st2, out = real(base, tables, st, op, val, *a, **k)
        bump = 1000 * (st.step_no == 3).astype(st2.msg_count.dtype)
        mc = st2.msg_count.at[3].add(bump).at[11].add(bump)
        return st2._replace(msg_count=mc), out
    return step


@pytest.mark.parametrize("fault,number", [
    (_stuck, "incomplete"), (_half, "incomplete"), (_value, "data"),
    (_message, "messages"), (_nack, "messages")],
    ids=["stuck", "half", "value", "message", "nack"])
def test_fault_fails(plant, fault, number):
    plant(fault)
    res = run(tiny_cell(CELLS[0]))
    assert not res["correct"]
    assert res["checks"][number]["value"] > res["checks"][number]["limit"]


def _pad(real):
    """A remote row that the fleet pads a member with, left non-zero."""
    def step(base, tables, st, op, val, *a, **k):
        st2, out = real(base, tables, st, op, val, *a, **k)
        ag = st2.agents
        return st2._replace(agents=ag._replace(
            pending_req=ag.pending_req.at[-1, -1].set(1))), out
    return step


@pytest.mark.parametrize("name", FLEETS)
@pytest.mark.parametrize("fault,number", [
    (_stuck, "incomplete"), (_half, "incomplete"), (_value, "data"),
    (_pad, "states")], ids=["stuck", "half", "value", "pad"])
def test_fault_fails_a_fleet(plant, fault, number, name):
    plant(fault)
    res = run(tiny_cell(name))
    assert not res["correct"] and res["failed"] == res["attempted"] == 1
    assert res["checks"][number]["value"] > res["checks"][number]["limit"]
