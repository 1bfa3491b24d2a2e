"""Vmapped sim fleets: one compiled program per bench sweep.

``bench_smoke``/``paper_benches`` sweeps used to pay one trace+compile of
the fused streaming scan PER POINT — an R x W grid or an H in {1,2,4}
homes sweep recompiled a structurally identical program once per member,
and compile time dominated CI wall clock.  ``run_fleet`` batches the
whole sweep into ONE jitted program: ``jax.vmap`` over the driver's
``run`` body, members stacked on a leading sweep axis.

What makes the members batchable (see ``config.FleetConfig`` for the
exact rules):

* **remotes** — every member runs at the fleet-wide R-max; narrower
  members pad their workload with NOP columns and their state with idle
  remotes.  Padded remotes are never ready, so arbitration picks the
  same winners (the rotating pointer stays within the real participant
  range and cyclic priority order is modulus-invariant there), and they
  drain their NOP streams faster than any real remote, so the
  active-step accounting is untouched — per-member counters are
  BIT-identical to the solo run.
* **width** — one W-max window; a traced per-member ``width_cap`` masks
  the slots past the member's real width (activation AND the
  fresh-slot boundary, so no stale born stamps leak into latencies).
* **homes / home_bw** — members ride the engine's flat-layout H-home
  emulation (``step_mn``'s ``home_group``/``home_bw_t`` operands): VC
  parity follows the folded plane-local line index and per-home
  acceptance is capped in the folded rotating order, bit-identical to
  the ``[H, R, L/H]`` fold while VC credits never bind (which
  ``FleetConfig`` validates).

Per-member results are bit-identical to solo ``run_stream`` runs AT THE
FLEET'S SHARED STEP BUDGET (``tests/test_fleet.py`` pins this): the
budget is the max of the members' ``default_steps``, and a solo run you
compare against must use the same number (counter fields like ``steps``
count the whole scan).

``FleetConfig.mesh_devices > 0`` shards the member axis across devices
— chips on a TPU host, forced host devices on the CPU — (``shard_map``
over a 1-D "fleet" mesh in the driver; each member's operands and state
are placed straight onto its device, never stacked on one): members
are independent, so each device runs the identical vmapped program on
its slice and per-member results stay bit-identical to the
single-device fleet.  Ragged member counts pad to a device multiple by
repeating the last member — the pad rows compute and are dropped on
readout, exactly like the NOP remote columns.

``run_fleet`` returns plain per-member ``StreamRun`` records; the
returned ``state`` is the member's R-max-padded flat engine state (rows
past the member's real remote count are idle).
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..core.engine_mn import make_engine_mn_state
from .config import FleetConfig
from .counters import RetirementTrace
from .driver import (StreamRun, _jitted_stream, default_steps,
                     fleet_mesh)


def _member(x: jax.Array, i: int) -> jax.Array:
    """Member ``i`` of a stacked leaf, sliced on the device that holds it:
    indexing a "fleet"-sharded array would gather the slice onto one
    device, and a full-size member's state is gigabytes."""
    for shard in x.addressable_shards:
        rows = range(x.shape[0])[shard.index[0]]
        if i in rows:
            return shard.data[i - rows.start]
    raise IndexError(f"fleet member {i} is on no local device")


def fleet_steps(fleet: FleetConfig) -> int:
    """The shared step budget ``run_fleet`` will use — exposed so solo
    comparison/benchmark runs can pin the SAME budget."""
    if fleet.steps:
        return fleet.steps
    return max(default_steps(s.workload.ops, e.remotes)
               for e, s in fleet.members)


def run_fleet(fleet: FleetConfig) -> List[StreamRun]:
    """Run every member of the sweep in one jitted, vmapped program.

    Compiles once for the whole fleet (per (subset, trace?, W-max,
    backend, S/R-max/L/T shape) key — a second fleet with the same
    shapes reuses the program), then reads each member's results back
    out of the stacked carry.  See the module docstring for the
    bit-identity contract.
    """
    # host spans on the profiler's clock, as in ``run_stream``.
    with TraceAnnotation("eci.prepare"):
        members = fleet.members
        engines = [e.build() for e, _ in members]
        e0, s0 = members[0]
        R_max = max(e.remotes for e, _ in members)
        W_max = max(s.width for _, s in members)
        steps = fleet_steps(fleet)
        mesh_n = int(fleet.mesh_devices)
        if mesh_n:
            avail = len(jax.devices())
            if mesh_n > avail:
                platform = jax.devices()[0].platform
                hint = (f"on CPU expose more with XLA_FLAGS="
                        f"--xla_force_host_platform_device_count={mesh_n} "
                        f"before importing jax" if platform == "cpu" else
                        f"run on a {platform} host with at least {mesh_n} "
                        f"chips")
                raise ValueError(
                    f"mesh_devices={mesh_n} but only {avail} {platform} "
                    f"device(s) are visible — {hint}")

        # materialize + subset-check each member's workload at its own
        # [T, R_m], then pad to the fleet plane with NOP columns.
        wls = []
        for eng, (e, s) in zip(engines, members):
            wl = s.workload.materialize(e.remotes, e.lines)
            if not eng.subset.check_workload(np.asarray(wl.op),
                                             n_remotes=e.remotes):
                raise ValueError(
                    f"fleet member workload outside subset "
                    f"'{eng.subset.name}' guarantee (allowed ops: "
                    f"{sorted(eng.subset.allowed_ops(e.remotes))})")
            wls.append(wl)
        T = int(np.asarray(wls[0].op).shape[0])

        def pad_cols(a):
            a = np.asarray(a)
            out = np.zeros((T, R_max), a.dtype)
            out[:, :a.shape[1]] = a
            return out

        # under a mesh the member axis pads to a device multiple by repeating
        # the last member; pad rows compute independently and are never read
        # back.
        n_real = len(members)
        rows = list(range(n_real))
        if mesh_n and n_real % mesh_n:
            rows += [n_real - 1] * (mesh_n - n_real % mesh_n)

        # every per-member operand is stacked on the host and placed straight
        # onto its member's device ("fleet"-sharded under a mesh) — no stacked
        # copy ever lands on one device first.
        sharding = None
        if mesh_n:
            from jax.sharding import NamedSharding, PartitionSpec as P
            sharding = NamedSharding(fleet_mesh(mesh_n), P("fleet"))

        def stack(per_member):
            return jax.device_put(
                np.stack([np.asarray(per_member[i]) for i in rows]), sharding)

        wl_op = stack([pad_cols(w.op) for w in wls])
        wl_line = stack([pad_cols(w.line) for w in wls])
        wl_value = stack([pad_cols(w.value) for w in wls])
        delays = stack([eng.delays for eng in engines])
        credits = stack([eng.credits for eng in engines])
        width_cap = stack([np.int32(s.width) for _, s in members])
        home_group = stack([np.int32(e.homes) for e, _ in members])
        home_bw_t = stack([np.int32(e.home_bw) for e, _ in members])

        # fresh R-max states (padded remotes start — and stay — idle), made
        # on the device(s) that run them.
        def fresh_states():
            st1 = make_engine_mn_state(
                jnp.zeros((e0.lines, e0.block), jnp.float32), R_max,
                packed=e0.packed)
            return jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (len(rows),) + a.shape), st1)

        st = jax.jit(fresh_states, **(
            {"out_shardings": sharding} if sharding else {}))()

        # the multi-home plane is EMULATED (home_group), so the program keys
        # on the flat layout; shared_credits/obs/open-loop are out of fleet
        # scope by FleetConfig validation.
        fn = _jitted_stream(engines[0].subset.name, s0.collect_trace, W_max,
                            False, 1, 0, None, False, 0, 0,
                            engines[0].kernel_backend, True, mesh_n)
        tsteps = jnp.arange(steps, dtype=jnp.int32)
    with TraceAnnotation("eci.dispatch"):
        if mesh_n:
            # the sharded entry point takes no filter/arrival operands (they
            # are out of fleet scope and shard_map specs cover real args).
            carry, completed = fn(st, wl_op, wl_line, wl_value, tsteps,
                                  delays, credits,
                                  width_cap, home_group, home_bw_t)
        else:
            carry, completed = fn(st, wl_op, wl_line, wl_value, tsteps,
                                  delays, credits, None, None, None,
                                  width_cap, home_group, home_bw_t)
    with TraceAnnotation("eci.readback"):
        completed = np.asarray(completed)
        retire = np.asarray(carry.retire) if s0.collect_trace else None
        ctr_all = jax.device_get(carry.ctr)
        msg_all = np.asarray(carry.st.msg_count, np.int64)
        pay_all = np.asarray(carry.st.payload_msgs)
        runs = []
        for i, (eng, (e, s), wl) in enumerate(zip(engines, members, wls)):
            R_m = e.remotes
            ctr = jax.tree_util.tree_map(lambda x: x[i], ctr_all)
            # the three per-remote counter planes carry padded rows (all
            # zero except lat_hist's never-touched rows) — slice them off so
            # the record is indistinguishable from the solo run's.
            ctr = ctr._replace(lat_hist=ctr.lat_hist[:R_m],
                               max_wait=ctr.max_wait[:R_m],
                               retired=ctr.retired[:R_m])
            trace = None
            if s0.collect_trace:
                trace = RetirementTrace(
                    retire_step=retire[i][:-1, :R_m],
                    op=np.asarray(wl.op), line=np.asarray(wl.line),
                    value=np.asarray(wl.value), n_lines=e.lines)
            runs.append(StreamRun(
                state=jax.tree_util.tree_map(lambda x: _member(x, i),
                                             carry.st),
                counters=ctr,
                msg_count=msg_all[i],
                payload_msgs=int(pay_all[i]),
                trace=trace,
                completed=bool(completed[i]),
            ))
        return runs
