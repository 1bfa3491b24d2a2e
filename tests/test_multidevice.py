"""Multi-device semantics tests.

These run in a SUBPROCESS with ``--xla_force_host_platform_device_count=8``
(the main test process must keep seeing 1 device), exercising the real
collectives: pushdown select/lookup/regex across 8 shards, int8
error-feedback gradient all-reduce, multi-stage pipeline parallelism, and a
2x2x2 multi-pod mesh train step.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(body: str) -> dict:
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        assert len(jax.devices()) == 8
        result = {}
    """) + textwrap.dedent(body) + "\nprint('RESULT::' + json.dumps(result))"
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, out.stderr[-4000:]
    for line in out.stdout.splitlines():
        if line.startswith("RESULT::"):
            return json.loads(line[len("RESULT::"):])
    raise AssertionError(f"no RESULT:: in stdout: {out.stdout[-2000:]}")


def test_pushdown_select_8shards():
    r = run_sub("""
        from repro.core.pushdown import pushdown_select
        from repro.nmp import make_table, select_scan
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("x",))
        t = make_table(jax.random.key(0), 1024, 8, 0.2)
        res = pushdown_select(mesh, "x", 128, t, 0.0, 1.0)
        _, count_ref, _ = select_scan(t, 0.0, 1.0)
        result["counts"] = [int(c) for c in res.counts]
        result["total"] = int(res.moved_rows)
        result["ref"] = int(count_ref)
    """)
    assert r["total"] == r["ref"]
    assert len(r["counts"]) == 8


def test_pushdown_lookup_8shards():
    r = run_sub("""
        from repro.core.pushdown import build_sharded_kvs, pushdown_lookup
        from repro.nmp import build_kvs, kvs_lookup
        keys = np.arange(1, 2001, dtype=np.uint32)
        vals = np.stack([keys.astype(np.float32)] * 2, 1)
        skvs = build_sharded_kvs(keys, vals, 256, 8)
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("x",))
        q = jnp.asarray([1, 500, 1999, 4242], jnp.uint32)
        v, found, steps = pushdown_lookup(mesh, "x", skvs, q, 64)
        result["found"] = [bool(f) for f in found]
        result["vals"] = [float(x) for x in v[:, 0]]
    """)
    assert r["found"] == [True, True, True, False]
    assert r["vals"][:3] == [1.0, 500.0, 1999.0]


def test_compressed_psum_matches_exact():
    r = run_sub("""
        from repro.optim import compression
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("pod",))
        g = jax.random.normal(jax.random.key(1), (8, 64)) * 0.1

        def f(gl, el):
            mean, e2 = compression.compressed_psum(gl[0], el[0], "pod")
            return mean, e2[None]
        fn = jax.shard_map(f, mesh=mesh, in_specs=(P("pod"), P("pod")),
                           out_specs=(P(), P("pod")), check_vma=False)
        err = jnp.zeros((8, 64))
        mean, err = fn(g, err)
        exact = g.mean(axis=0)
        result["rel_err"] = float(jnp.linalg.norm(mean - exact)
                                  / jnp.linalg.norm(exact))
    """)
    assert r["rel_err"] < 0.02, r


def test_pipeline_4stages_matches_serial():
    r = run_sub("""
        from repro.runtime import pipeline_apply
        mesh = Mesh(np.array(jax.devices()).reshape(8)[:4].reshape(4),
                    ("stage",)) if False else Mesh(
                    np.array(jax.devices()).reshape(8, 1)[:4].reshape(4),
                    ("stage",))
        # 4 stages, each multiplies by its own factor and adds its bias.
        ws = jnp.stack([jnp.full((2,), 1.0 + i) for i in range(4)])
        def layer(w, x):
            return x * w[0] + w[1] * 0.0 + 1.0
        xm = jnp.arange(24, dtype=jnp.float32).reshape(6, 4)
        out = pipeline_apply(mesh, "stage", layer, ws, xm)
        ref = xm
        for i in range(4):
            ref = ref * (1.0 + i) + 1.0
        result["max_err"] = float(jnp.abs(out - ref).max())
    """)
    assert r["max_err"] == 0.0, r


def test_multipod_train_step_2x2x2():
    r = run_sub("""
        from repro.configs import get_config
        from repro.models import init_params
        from repro.optim import OptimConfig
        from repro.train.train_step import init_state, make_train_step
        from repro.data import DataConfig, SyntheticPipeline
        mesh = Mesh(np.array(jax.devices()).reshape(2, 2, 2),
                    ("pod", "data", "model"))
        cfg = get_config("smollm-360m", smoke=True)
        params = init_params(jax.random.key(0), cfg)
        step = make_train_step(cfg, OptimConfig(total_steps=10), mesh,
                               params, donate=False)
        state = init_state(params)
        pipe = SyntheticPipeline(DataConfig(cfg.vocab, 16, 8), mesh)
        losses = []
        for i in range(3):
            state, m = step(state, pipe.batch(i))
            losses.append(float(m["loss"]))
        result["losses"] = losses
    """)
    assert all(np.isfinite(l) for l in np.asarray(r["losses"]))
    assert len(r["losses"]) == 3


def test_sharded_fleet_bit_identical_to_solo():
    """``FleetConfig.mesh_devices`` shards the member axis over host
    devices, one member's state per device; every member's
    counters/msg_count must equal BOTH the
    single-device fleet's and the solo ``run_stream`` run's, including a
    ragged member count that pads by repeating the last member."""
    r = run_sub("""
        from repro.traffic import (EngineConfig, FleetConfig, StreamConfig,
                                   WorkloadSpec, fleet_steps, run_fleet,
                                   run_stream)
        members = tuple(
            (EngineConfig(remotes=rm, lines=16),
             StreamConfig(workload=WorkloadSpec("zipfian", ops=12, seed=5),
                          width=w))
            for rm in (4, 6) for w in (1, 2))
        solo_fleet = run_fleet(FleetConfig(members=members))
        shard = run_fleet(FleetConfig(members=members, mesh_devices=4))
        steps = fleet_steps(FleetConfig(members=members))
        ok = True
        for (e, s), a, b in zip(members, solo_fleet, shard):
            solo = run_stream(e.build(), StreamConfig(
                workload=s.workload, width=s.width, steps=steps))
            for ref in (a, solo):
                ok &= bool((np.asarray(ref.counters.retired)
                            == np.asarray(b.counters.retired)).all())
                ok &= bool((np.asarray(ref.counters.lat_hist)
                            == np.asarray(b.counters.lat_hist)).all())
                ok &= (np.asarray(ref.msg_count)
                       == np.asarray(b.msg_count)).all().item()
                ok &= ref.completed == b.completed
        # ragged: 3 members on 2 devices pads to 4 rows
        m3 = members[:3]
        for a, b in zip(run_fleet(FleetConfig(members=m3)),
                        run_fleet(FleetConfig(members=m3, mesh_devices=2))):
            ok &= bool((np.asarray(a.counters.retired)
                        == np.asarray(b.counters.retired)).all())
            ok &= (np.asarray(a.msg_count)
                   == np.asarray(b.msg_count)).all().item()
        result["ok"] = bool(ok)
        result["n"] = len(shard)
        # each member's state stays on the device that ran it — never
        # gathered onto one device.
        result["homes"] = [
            sorted({d.id for leaf in jax.tree_util.tree_leaves(b.state)
                    for d in leaf.devices()}) for b in shard]
    """)
    assert r["ok"], r
    assert r["n"] == 4
    assert r["homes"] == [[0], [1], [2], [3]]


def test_multipod_decode_2x2x2():
    r = run_sub("""
        from repro.configs import get_config
        from repro.models import init_params, init_decode_state
        from repro.serve import make_serve_step
        mesh = Mesh(np.array(jax.devices()).reshape(2, 2, 2),
                    ("pod", "data", "model"))
        cfg = get_config("gemma2-9b", smoke=True)
        params = init_params(jax.random.key(0), cfg)
        state = init_decode_state(cfg, 8, 32)
        step = make_serve_step(cfg, mesh, state, params, donate=False)
        tok = jnp.zeros((8,), jnp.int32)
        lg, state = step(params, tok, jnp.asarray(0, jnp.int32), state)
        result["shape"] = list(lg.shape)
        result["finite"] = bool(jnp.isfinite(lg).all())
    """)
    assert r["shape"] == [8, 256]
    assert r["finite"]
