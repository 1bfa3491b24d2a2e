"""Quiescence-free streaming driver for the N-remote coherency engine.

Every in-repo driver before this one drained the engine to quiescence
after each op round, so ``EngineMN.step`` never saw sustained, overlapping
traffic — the ROADMAP's latent arbitration starvation was untestable and
throughput unmeasurable.  This driver issues new ops from every remote's
stream EVERY step, while prior transactions are still in flight:

* **backpressure** comes from the engine itself: an op the engine cannot
  take this step (line transaction in flight, channel slot busy, VC out of
  credit) is simply not in the ``accepted`` mask and the slot's op is
  retried next step;
* each remote keeps a WINDOW of up to ``width`` head-of-stream ops pending
  acceptance (its per-remote ``[R, W]`` issue queue) and up to L
  transactions in flight across lines — the overlap a real initiator's
  MSHRs provide.  MSHR allocation stays ONE per (remote, line): window
  slots targeting the line of an earlier un-issued slot (or of an
  in-flight transaction) are serialized in-queue, so per-line program
  order is preserved while independent lines issue out of order, exactly
  like a real non-blocking cache;
* the whole run is ONE fused ``lax.scan`` over engine steps — python never
  appears in the hot loop; issue, bookkeeping and the perf counters of
  ``traffic.counters`` all fold through the scan carry, and the engine
  state is DONATED into the program so the ``[R, L]`` slabs update in
  place.

Retirement is detected uniformly: an accepted op is retired once the
agent's MSHR for its line is clear again (hits clear it the same step;
misses when the grant lands).  The optional retirement TRACE — which op
retired when — is the linearization ``traffic.counters`` replays into the
atomic ``MultiNodeRef`` to validate the message counters exactly; the
replay argument is per-line retirement order, which multi-op issue leaves
untouched (same-line ops stay in program order, cross-line ops commute in
the atomic oracle), so counter exactness holds at every width.

**Open-loop serving** (``StreamConfig.arrivals``): each workload slot
carries an arrival step (``traffic.arrivals``), and a continuous-batching
admission loop runs inside the same fused scan — a slot becomes an issue
candidate only once it has ARRIVED, and (when ``StreamConfig.admission``
caps the batch) only while global in-flight count sits below
``max_inflight - reserve``, with the candidate set admitted FIFO by
arrival stamp.  Admission gates WHEN an op enters flight, never what it
does, so the retirement-order oracle replay above stays exact; what
changes is the measurement: sojourn (arrival -> retirement) and admission
wait fold into dedicated histograms (``SOJOURN_EDGES``) carried separately
from ``Counters``, so a closed-loop-equivalent schedule (all arrivals at
step 0, no cap) leaves every existing counter bit-identical.
"""
from __future__ import annotations

import functools
import warnings
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..core.engine_mn import EngineMN, EngineMNState, busy_flag_mn, step_mn
from ..core.messages import MsgType
from ..core.protocol import LocalOp, mn_tables
from .arrivals import ArrivalSchedule, check_schedule
from .config import (AdmissionConfig, ArrivalSpec, StreamConfig,
                     WorkloadSpec)
from .counters import (Counters, N_SOJ_BUCKETS, RetirementTrace,
                       SOJOURN_EDGES, bucket_counts, make_counters,
                       update_counters)
from .observe import (ObserveConfig, ObsResult, _encoded_tables,
                      compiled_specs, finalize_obs, fold_obs,
                      make_obs_carry)
from .workloads import Workload

# the issue window scatters ops/values ADDITIVELY into the dense [R, L]
# planes (at most one contributing slot per (remote, line), the rest add
# the identity) — which requires NOP to be the zero code.
assert int(LocalOp.NOP) == 0 and int(MsgType.NOP) == 0


class _Soj(NamedTuple):
    """Open-loop serving telemetry, carried SEPARATELY from ``Counters``
    so closed-loop-equivalent open-loop runs keep those bit-identical."""

    born: jnp.ndarray   # [R, L] int32: arrival step of the in-flight txn
    hist: jnp.ndarray   # [N_SOJ_BUCKETS] int32: sojourn histogram
    admit: jnp.ndarray  # [N_SOJ_BUCKETS] int32: admission-wait histogram


class _Carry(NamedTuple):
    st: EngineMNState
    cursor: jnp.ndarray       # [R] int32: stream index of window slot 0
    issued: jnp.ndarray       # [R, W] bool: slot accepted (or NOP-skipped)
    slot_born: jnp.ndarray    # [R, W] int32: step the slot entered the window
    outstanding: jnp.ndarray  # [R, L] bool: accepted, not yet retired
    born: jnp.ndarray         # [R, L] int32: first-attempt step per txn
    out_idx: jnp.ndarray      # [R, L] int32: stream index of in-flight txn
    #                           (trace mode; [0] placeholder otherwise)
    retire: jnp.ndarray       # [T+1, R] int32: retirement step per stream
    #                           slot, -1 = in flight; row T is a scratch
    #                           row non-retiring lanes scatter into (trace
    #                           mode; [0] placeholder otherwise)
    ctr: Counters
    obs: object = None        # ObsCarry when observability is enabled;
    #                           None (an empty pytree) otherwise
    soj: object = None        # _Soj for open-loop runs; None otherwise


def default_steps(ops: int, n_remotes: int, last_arrival: int = 0) -> int:
    """Step budget covering an ``ops``-per-remote stream plus drain tail.

    Sustained throughput saturates near 1 op/step under hot-line
    contention, so the budget must scale with TOTAL ops (R * ops), not
    per-remote ops — a fixed multiple of ``ops`` strands wide runs with
    ``completed=False``.  (Issue width can only bring retirement EARLIER,
    so the width-1 budget is safe at every width; steps on a drained
    engine are no-ops, so the generous tail only costs device time.)

    ``last_arrival`` extends the budget for OPEN-LOOP runs: an op that
    arrives at step ``a`` cannot retire before it, so the closed-loop
    budget shifts out by the latest arrival stamp.  This is the ONE
    shared auto-derivation helper — the driver (``steps=0``), the CLI
    (``--steps 0``) and ``bench_smoke`` all call it."""
    return 2 * ops * n_remotes + 12 * ops + 64 + int(last_arrival)


class StreamRun(NamedTuple):
    """Result of one streaming run."""

    state: EngineMNState
    counters: Counters
    msg_count: np.ndarray     # [16] int64: delivered messages, this run
    payload_msgs: int         # messages that carried line data, this run
    trace: Optional[RetirementTrace]
    completed: bool           # stream fully consumed AND engine quiescent
    obs: Optional[ObsResult] = None   # observability digest (observe=...)
    # ---- open-loop serving results (cfg.arrivals set; else None/0) ------
    sojourn_hist: Optional[np.ndarray] = None     # [N_SOJ_BUCKETS] int64
    admit_wait_hist: Optional[np.ndarray] = None  # [N_SOJ_BUCKETS] int64
    backlog: int = 0          # arrived-but-never-issued ops at budget end
    #                           (> 0 = unserved queue growth: overload)


def fleet_mesh(n_devices: int):
    """The 1-D "fleet" mesh over the first ``n_devices`` devices — the
    one mesh both the sharded fleet program and ``run_fleet``'s member
    placement use."""
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:n_devices]), ("fleet",))


@functools.lru_cache(maxsize=None)
def _jitted_stream(subset_name: str, collect_trace: bool, width: int,
                   hreq_shared: bool = False, n_homes: int = 1,
                   home_bw: int = 0,
                   obs: Optional[ObserveConfig] = None,
                   open_loop: bool = False, admit_cap: int = 0,
                   admit_reserve: int = 0,
                   kernel_backend: str = "xla",
                   fleet: bool = False,
                   mesh_devices: int = 0):
    """One fused streaming program per (subset, trace?, width, credit
    model, home plane, observability, admission, kernel backend) tuple,
    shared across engines; shapes (R, L, T, total steps) retrace inside
    jit's cache.  The engine state is donated — the streaming scan is the
    hot path, and per-step reallocation of the ``[R, L]`` slabs is pure
    overhead.  ``obs=None`` (the default) leaves the traced program
    EXACTLY what it always was — observability is compiled in only when
    an ``ObserveConfig`` keys a separate cache entry, and likewise
    ``open_loop=False`` compiles no arrival/admission logic at all.
    ``admit_cap``/``admit_reserve`` are STATIC (they key the program), so
    a knee sweep varying only the arrival schedule reuses one compiled
    program.

    ``fleet=True`` (``traffic.fleet``) vmaps the SAME per-member program
    over a leading sweep axis and takes three extra TRACED per-member
    operands: ``width_cap`` (the member's real issue width — ``width``
    then is the fleet-wide max, slots past the cap never activate),
    ``home_group``/``home_bw_t`` (the engine's flat-layout H-home
    emulation).  A fleet member's body is bit-identical to its solo
    program at the same step budget.

    ``mesh_devices > 0`` (fleet only) additionally shards the vmapped
    member axis across that many devices via ``shard_map`` over a
    1-D "fleet" mesh — members are data-parallel and fully independent,
    so each device runs the identical per-member program on its slice
    and results stay bit-identical to the single-device fleet (gated in
    ``tests/test_multidevice.py``).  The member axis must be a multiple
    of ``mesh_devices`` (``run_fleet`` pads by repeating members)."""
    tables_mn = mn_tables(subset_name)
    step_fn = functools.partial(step_mn, tables_mn.base, tables_mn,
                                hreq_shared=hreq_shared, n_homes=n_homes,
                                home_bw=home_bw,
                                kernel_backend=kernel_backend)
    nop_op = jnp.int8(int(LocalOp.NOP))
    W = width
    if obs is not None:
        comp = compiled_specs(obs.specs)
        tab_np, start_np = _encoded_tables(comp)

    def run(st, wl_op, wl_line, wl_value, tsteps, delays, credits,
            line_filt=None, type_filt=None, arr_step=None,
            width_cap=None, home_group=None, home_bw_t=None):
        # the agent plane is dense under every directory layout (packed
        # states carry [2, L, W] uint32 slabs instead of [R, L] int8).
        R, L = st.agents.remote_state.shape
        B = st.dir.backing.shape[1]
        T = wl_op.shape[0]
        dt = st.dir.backing.dtype
        ar = jnp.arange(R)
        wr = jnp.arange(W)
        zb = jnp.zeros((L,), bool)
        zwv = jnp.zeros((L, B), dt)

        def body(c, t):
            # ---- fetch each remote's issue window -----------------------
            with jax.named_scope("eci.issue"):
                idx = c.cursor[:, None] + wr[None, :]            # [R, W]
                active = idx < T
                if fleet:
                    # window slots past the member's real width never
                    # activate — the member behaves exactly as if its window
                    # were width_cap wide while the fleet compiles one W-max
                    # shaped program.
                    active = active & (wr[None, :] < width_cap)
                idxc = jnp.minimum(idx, T - 1)
                s_op = wl_op[idxc, ar[:, None]]                  # [R, W]
                s_line = wl_line[idxc, ar[:, None]]
                s_val = wl_value[idxc, ar[:, None]].astype(dt)
                is_nop = s_op == nop_op
                pending = active & ~c.issued
                real = pending & ~is_nop
                # one MSHR per (remote, line): a slot is serialized in-queue
                # behind an EARLIER un-issued slot on the same line, and held
                # while the remote still has a transaction in flight there.
                # The conflict mask deliberately uses ALL queued real slots
                # (arrived or not) so per-line program order survives any
                # arrival schedule.
                same = s_line[:, :, None] == s_line[:, None, :]  # [R, Wk, Wj]
                earlier = wr[None, :] < wr[:, None]              # [Wk, Wj] j<k
                conflict = (real[:, None, :] & same &
                            earlier[None]).any(-1)               # [R, W]
                line_busy = c.outstanding[ar[:, None], s_line]
                if open_loop:
                    # ---- continuous-batching admission ----------------
                    # a slot is a candidate only once its stamp has ARRIVED;
                    # with a batch cap, the FIFO-by-arrival-stamp earliest
                    # candidates fill the budget the reserve watermark leaves
                    # open (rtp-llm FIFOScheduler style) — admission gates
                    # WHEN, never WHAT, so the oracle replay stays exact.
                    s_arr = arr_step[idxc, ar[:, None]]          # [R, W]
                    arrived = s_arr <= t
                    ready = real & arrived & ~conflict & ~line_busy
                    if admit_cap:
                        inflight = c.outstanding.sum().astype(jnp.int32)
                        budget = jnp.maximum(
                            admit_cap - admit_reserve - inflight, 0)
                        # stable argsort = FIFO by stamp, program order on
                        # ties; non-candidates sort to the back.
                        key = jnp.where(ready, s_arr,
                                        jnp.iinfo(jnp.int32).max).ravel()
                        order = jnp.argsort(key, stable=True)
                        rank = jnp.zeros_like(order).at[order].set(
                            jnp.arange(R * W))
                        can = ready & (rank.reshape(R, W) < budget)
                    else:
                        can = ready
                else:
                    can = real & ~conflict & ~line_busy
                # scatter the issuable slots into the dense [R, L] op plane —
                # additive scatter: at most one slot per (remote, line)
                # contributes a non-zero, the rest add NOP/zero.
                opd = jnp.zeros((R, L), jnp.int8).at[ar[:, None], s_line].add(
                    jnp.where(can, s_op, nop_op))
                vald = jnp.zeros((R, L, B), dt).at[ar[:, None], s_line].add(
                    jnp.where(can, s_val, 0)[:, :, None])
                born_d = jnp.zeros((R, L), jnp.int32).at[
                    ar[:, None], s_line].add(jnp.where(can, c.slot_born, 0))
                if open_loop:   # arrival stamp rides along for sojourn
                    soj_d = jnp.zeros((R, L), jnp.int32).at[
                        ar[:, None], s_line].add(jnp.where(can, s_arr, 0))

            # ---- one engine step under sustained traffic ----------------
            with jax.named_scope("eci.step"):
                hk = {"home_group": home_group,
                      "home_bw_t": home_bw_t} if fleet else {}
                if obs is None:
                    st2, out = step_fn(c.st, opd, vald, zb, zb, zwv, delays,
                                       credits, **hk)
                else:
                    st2, out, ev = step_fn(c.st, opd, vald, zb, zb, zwv,
                                           delays, credits, emit_events=True,
                                           **hk)

            # ---- adopt newly accepted ops, detect retirements -----------
            with jax.named_scope("eci.retire"):
                newly = out.accepted                       # [R, L]
                outstanding = c.outstanding | newly
                born = jnp.where(newly, born_d, c.born)
                # retired once the MSHR is clear again: hits the same step,
                # misses when the grant (or NACK-retry grant) lands.
                mshr_free = (st2.agents.pending_op == int(LocalOp.NOP)) & \
                            (st2.agents.pending_req == int(MsgType.NOP))
                retired = outstanding & mshr_free
                outstanding = outstanding & ~retired

                # ---- compact retirement record (trace mode) -------------
                out_idx, retire = c.out_idx, c.retire
                if collect_trace:
                    # stream index of each in-flight transaction; retiring
                    # lanes stamp the step into their slot's row, everything
                    # else lands in the scratch row T (sliced off on readout).
                    idx_d = jnp.zeros((R, L), jnp.int32).at[
                        ar[:, None], s_line].add(jnp.where(can, idxc, 0))
                    out_idx = jnp.where(newly, idx_d, c.out_idx)
                    row = jnp.where(retired, out_idx, T)         # [R, L]
                    retire = c.retire.at[row, ar[:, None]].set(t)

                # ---- sojourn + admission-wait histograms (open loop) ----
                soj = c.soj
                slot_acc = can & newly[ar[:, None], s_line]      # [R, W]
                if open_loop:
                    soj_born = jnp.where(newly, soj_d, soj.born)
                    s_lat = t - soj_born                         # [R, L]
                    hist = soj.hist + bucket_counts(
                        s_lat, retired, SOJOURN_EDGES, axis=(0, 1))
                    admit = soj.admit + bucket_counts(
                        t - s_arr, slot_acc, SOJOURN_EDGES, axis=(0, 1))
                    soj = _Soj(born=soj_born, hist=hist.astype(jnp.int32),
                               admit=admit.astype(jnp.int32))

                # ---- slide each window past its issued prefix -----------
                nop_skip = pending & is_nop
                if open_loop:   # a NOP slot is consumed at its arrival, not
                    nop_skip = nop_skip & arrived    # before (FIFO stamps)
                issued = c.issued | slot_acc | nop_skip
                shift = jnp.cumprod(issued.astype(jnp.int32), axis=1).sum(1)
                cursor = c.cursor + shift
                k2 = wr[None, :] + shift[:, None]                # [R, W]
                # a slot sliding in from past the member's window is FRESH
                # (born now) — under a fleet the boundary is the member's
                # width_cap, not the compiled W-max, or masked slots' stale
                # born stamps would leak into real slots' latency metrics.
                in_w = (k2 < width_cap) if fleet else (k2 < W)
                k2c = jnp.minimum(k2, W - 1)
                issued2 = jnp.where(in_w,
                                    jnp.take_along_axis(issued, k2c, axis=1),
                                    False)
                slot_born = jnp.where(
                    in_w, jnp.take_along_axis(c.slot_born, k2c, axis=1), t + 1)

            # ---- hardware-style counters fold through the carry ---------
            with jax.named_scope("eci.counters"):
                lat = t - born
                waiting = active & ~issued                       # [R, W]
                head_wait = jnp.where(waiting, t - c.slot_born, 0).max(axis=1)
                # active = stream unconsumed or engine non-quiescent: the
                # denominator for sustained rates (the scan's generous drain
                # tail runs idle steps that must not dilute throughput).
                step_active = active.any() | busy_flag_mn(st2)
                ctr = update_counters(c.ctr, st2, retired=retired, lat=lat,
                                      outstanding=outstanding,
                                      head_wait=head_wait,
                                      step_active=step_active,
                                      backend=kernel_backend)

            # ---- observability plane (in-scan; compiled in only when
            # ---- an ObserveConfig keys this program) --------------------
            oc = c.obs
            if obs is not None:
                with jax.named_scope("eci.observe"):
                    oc = fold_obs(obs, jnp.asarray(tab_np),
                                  jnp.asarray(start_np), oc, ev, t,
                                  line_filt, type_filt,
                                  newly=newly, born_d=born_d, retired=retired)

            c2 = _Carry(st=st2, cursor=cursor, issued=issued2,
                        slot_born=slot_born,
                        outstanding=outstanding, born=born,
                        out_idx=out_idx, retire=retire, ctr=ctr, obs=oc,
                        soj=soj)
            return c2, None

        if collect_trace:
            out_idx0 = jnp.zeros((R, L), jnp.int32)
            retire0 = jnp.full((T + 1, R), -1, jnp.int32)
        else:   # zero-size placeholders: no per-step trace cost at all
            out_idx0 = jnp.zeros((0,), jnp.int32)
            retire0 = jnp.zeros((0,), jnp.int32)
        carry0 = _Carry(
            st=st,
            cursor=jnp.zeros((R,), jnp.int32),
            issued=jnp.zeros((R, W), bool),
            slot_born=jnp.zeros((R, W), jnp.int32),
            outstanding=jnp.zeros((R, L), bool),
            born=jnp.zeros((R, L), jnp.int32),
            out_idx=out_idx0,
            retire=retire0,
            ctr=make_counters(R),
            obs=(make_obs_carry(obs, R, L, comp)
                 if obs is not None else None),
            soj=(_Soj(born=jnp.zeros((R, L), jnp.int32),
                      hist=jnp.zeros((N_SOJ_BUCKETS,), jnp.int32),
                      admit=jnp.zeros((N_SOJ_BUCKETS,), jnp.int32))
                 if open_loop else None),
        )
        carry, _ = jax.lax.scan(body, carry0, tsteps)
        completed = (carry.cursor >= T).all() & \
            ~carry.outstanding.any() & ~busy_flag_mn(carry.st)
        return carry, completed

    if fleet:
        # one compiled program for the whole sweep: members batch over a
        # leading axis (state/workload/delays/credits/caps), the step
        # vector is shared.  Filters/arrivals are out of fleet scope
        # (validated by FleetConfig) and pass through as None.
        vm = jax.vmap(run, in_axes=(0, 0, 0, 0, None, 0, 0, None, None,
                                    None, 0, 0, 0))
        if mesh_devices:
            from jax.sharding import PartitionSpec as P
            mesh = fleet_mesh(mesh_devices)

            def sharded(st, wl_op, wl_line, wl_value, tsteps, delays,
                        credits, width_cap, home_group, home_bw_t):
                # per-member computation is independent — each device
                # runs the identical vmapped program over its member
                # slice, so the output is bit-identical to one device.
                return vm(st, wl_op, wl_line, wl_value, tsteps, delays,
                          credits, None, None, None, width_cap,
                          home_group, home_bw_t)

            fp = P("fleet")
            fn = jax.shard_map(sharded, mesh=mesh,
                               in_specs=(fp,) * 4 + (P(),) + (fp,) * 5,
                               out_specs=fp, check_vma=False)
            return jax.jit(fn, donate_argnums=0)
        return jax.jit(vm, donate_argnums=0)
    return jax.jit(run, donate_argnums=0)


def _check_filters(engine: EngineMN,
                   observe: Optional[ObserveConfig],
                   line_filter, type_filter) -> None:
    """Loud entry validation of the capture filters: a wrong-shaped or
    wrong-dtype numpy array used to escape as a traced broadcast failure
    deep inside the fused scan."""
    if (line_filter is not None or type_filter is not None) \
            and observe is None:
        raise ValueError(
            "line_filter/type_filter restrict the observability capture "
            "ring — they require observe=ObserveConfig(...)")
    for name, filt, shape, what in (
            ("line_filter", line_filter, (engine.n_lines,),
             "[n_lines]"),
            ("type_filter", type_filter, (16,), "[16] (MsgType-indexed)")):
        if filt is None:
            continue
        arr = np.asarray(filt)
        if arr.shape != shape:
            raise ValueError(
                f"{name} must be a {what} bool mask, shape {shape}; "
                f"got shape {arr.shape}")
        if arr.dtype != np.bool_:
            raise ValueError(
                f"{name} must have bool dtype; got {arr.dtype} "
                f"(pass np.asarray(..., bool))")


def run_stream(engine: EngineMN, wl, steps: int = 0,
               st: Optional[EngineMNState] = None,
               collect_trace: bool = False, width: int = 1,
               observe: Optional[ObserveConfig] = None,
               line_filter: Optional[np.ndarray] = None,
               type_filter: Optional[np.ndarray] = None) -> StreamRun:
    """Drive one streaming run: ``run_stream(engine, StreamConfig)``.

    The ``StreamConfig`` (``traffic.config``) is the single construction
    surface — workload (arrays or seeded ``WorkloadSpec``), optional
    open-loop arrival schedule + admission control, issue width, step
    budget (0 = auto via ``default_steps``), observability and capture
    filters, trace collection.  ``st`` optionally continues from an
    earlier run's state; the passed-in state is CONSUMED (donated to the
    fused program) — use the returned ``state``.

    The legacy kwarg form ``run_stream(engine, wl, steps, st,
    collect_trace, width, observe, line_filter, type_filter)`` still
    works: it forwards into the exact same config path (and thus the same
    cached jit program — pinned bit-identical in tests/test_serving.py)
    with a ``DeprecationWarning``.

    The WHOLE op stream is checked against the engine's protocol subset
    BEFORE anything is submitted (one vectorized pass over the ``[T, R]``
    plane, which covers every future ``[R, W]`` issue window) — an op
    that violates the guarantee only in the last slot of the last window
    still rejects the run up front, with the engine state untouched.
    """
    if isinstance(wl, StreamConfig):
        if steps or collect_trace or width != 1 or observe is not None \
                or line_filter is not None or type_filter is not None:
            raise TypeError(
                "run_stream(engine, StreamConfig) takes the run knobs "
                "from the config — set steps/width/observe/filters/"
                "collect_trace there, not as kwargs")
        return _run_config(engine, wl, st)
    warnings.warn(
        "run_stream(engine, wl, steps, ...) is deprecated; pass "
        "run_stream(engine, StreamConfig(workload=wl, steps=..., ...))",
        DeprecationWarning, stacklevel=2)
    return _run_config(engine, StreamConfig(
        workload=wl, width=width, steps=steps, observe=observe,
        line_filter=line_filter, type_filter=type_filter,
        collect_trace=collect_trace), st)


class _Program(NamedTuple):
    """A streaming run resolved up to the call: the validated workload,
    arrival schedule and step budget, the jitted program, and every
    operand after the engine state."""

    wl: Workload
    arr: Optional[ArrivalSchedule]
    steps: int
    fn: object
    operands: tuple


def _program(engine: EngineMN, cfg: StreamConfig) -> _Program:
    wl = cfg.workload
    if isinstance(wl, WorkloadSpec):
        wl = wl.materialize(engine.n_remotes, engine.n_lines)
    if not engine.subset.check_workload(np.asarray(wl.op),
                                        n_remotes=engine.n_remotes):
        raise ValueError(
            f"workload op stream outside subset "
            f"'{engine.subset.name}' guarantee (allowed ops: "
            f"{sorted(engine.subset.allowed_ops(engine.n_remotes))})")
    T = int(np.asarray(wl.op).shape[0])
    _check_filters(engine, cfg.observe, cfg.line_filter, cfg.type_filter)

    # ---- open-loop pieces: arrival schedule + admission ----------------
    open_loop = cfg.arrivals is not None
    adm = cfg.admission if cfg.admission is not None else AdmissionConfig()
    if adm.max_inflight and not open_loop:
        raise ValueError(
            "admission control needs an arrival schedule — set "
            "StreamConfig.arrivals (use arrivals.at_step0 for a "
            "closed-loop-equivalent run)")
    arr = None
    last_arrival = 0
    if open_loop:
        arr = cfg.arrivals
        if isinstance(arr, ArrivalSpec):
            arr = arr.materialize(T, engine.n_remotes)
        check_schedule(arr, T, engine.n_remotes)
        last_arrival = int(np.asarray(arr.step).max()) if T else 0
    steps = cfg.steps or default_steps(T, engine.n_remotes, last_arrival)

    fn = _jitted_stream(engine.subset.name, cfg.collect_trace,
                        int(cfg.width), engine.shared_credits,
                        engine.n_homes, engine.home_bw, cfg.observe,
                        open_loop, int(adm.max_inflight), int(adm.reserve),
                        engine.kernel_backend)
    # None filters/arrivals pass through as empty pytree leaves, so the
    # jit program specializes away the corresponding gathers entirely.
    lf = None if cfg.line_filter is None else \
        jnp.asarray(cfg.line_filter, bool)
    tf = None if cfg.type_filter is None else \
        jnp.asarray(cfg.type_filter, bool)
    arr_dev = None if arr is None else jnp.asarray(arr.step, jnp.int32)
    operands = (wl.op, wl.line, wl.value,
                jnp.arange(steps, dtype=jnp.int32),
                engine.delays, engine.credits, lf, tf, arr_dev)
    return _Program(wl, arr, steps, fn, operands)


def stream_program(engine: EngineMN, cfg: StreamConfig):
    """``(program, operands)``: the jitted program ``run_stream(engine,
    cfg)`` runs, with every operand (the engine state first) as a
    ``jax.ShapeDtypeStruct``.  ``program.lower(*operands).compile()``
    compiles it ahead of time — its text, cost and memory analysis —
    without allocating the state."""
    p = _program(engine, cfg)
    shape = lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a))
    return p.fn, (jax.eval_shape(engine.init),) + tuple(
        None if a is None else shape(a) for a in p.operands)


def _run_config(engine: EngineMN, cfg: StreamConfig,
                st: Optional[EngineMNState]) -> StreamRun:
    # host spans on the profiler's clock (no-ops unless a trace is
    # active): what the host does before, at and after the device program.
    with TraceAnnotation("eci.prepare"):
        p = _program(engine, cfg)
        wl, arr, steps = p.wl, p.arr, p.steps
        T = int(np.asarray(wl.op).shape[0])
        st0 = engine.init() if st is None else st
        base_msgs = np.asarray(st0.msg_count, np.int64)
        base_payload = int(st0.payload_msgs)
    with TraceAnnotation("eci.dispatch"):
        carry, completed = p.fn(st0, *p.operands)
    with TraceAnnotation("eci.readback"):
        trace = None
        if cfg.collect_trace:
            # compact O(T * R) record: the scratch row the non-retiring lanes
            # scatter into is sliced off; op/line/value come straight from
            # the workload, which the retire_step array indexes 1:1.
            trace = RetirementTrace(
                retire_step=np.asarray(carry.retire)[:-1],
                op=np.asarray(wl.op),
                line=np.asarray(wl.line),
                value=np.asarray(wl.value),
                n_lines=engine.n_lines,
            )
        obs_res = None
        if cfg.observe is not None:
            obs_res = finalize_obs(cfg.observe, carry.obs,
                                   compiled_specs(cfg.observe.specs))
        soj_hist = admit_hist = None
        backlog = 0
        if arr is not None:
            soj_hist = np.asarray(carry.soj.hist, np.int64)
            admit_hist = np.asarray(carry.soj.admit, np.int64)
            # backlog = arrived-but-never-issued ops when the budget ran out:
            # the cursor counts each remote's consumed prefix; non-contiguous
            # issued slots still sit in the window flags.
            arrived_total = int((np.asarray(arr.step) < steps).sum())
            cur = np.asarray(carry.cursor, np.int64)
            iss = np.asarray(carry.issued)
            idx = cur[:, None] + np.arange(int(cfg.width))[None, :]
            issued_total = int(cur.sum()) + int((iss & (idx < T)).sum())
            backlog = arrived_total - issued_total
        return StreamRun(
            state=carry.st,
            counters=jax.device_get(carry.ctr),
            msg_count=np.asarray(carry.st.msg_count, np.int64) - base_msgs,
            payload_msgs=int(carry.st.payload_msgs) - base_payload,
            trace=trace,
            completed=bool(completed),
            obs=obs_res,
            sojourn_hist=soj_hist,
            admit_wait_hist=admit_hist,
            backlog=backlog,
        )
