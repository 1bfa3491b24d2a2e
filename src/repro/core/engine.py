"""The two-node coherency engine: directory + agent + VC transport, wired.

This is the executable form of the whole ECI stack: a home node (directory +
backing store), a remote node (4-state caching agent), and four virtual-
channel classes between them with per-VC delays (cross-VC reordering) and
credit-based flow control.  The entire step function is one fused ``jit``
program over dense per-line arrays — the "hundreds of states" of a real
implementation exist here only as (stable state x pending transaction)
products, exactly the paper's framing.

Deadlock freedom: response classes have effectively unbounded credit (a
response can always sink — the standard argument for message-class
separation); request classes have finite credit and stall at submission.

Used by: the property/bisimulation tests, the ``CoherentStore`` user API,
and every microbenchmark that reproduces a paper figure.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import agent as ag
from . import directory as dr
from . import transport as tp
from .messages import MsgType
from .protocol import FULL, MINIMAL, DenseTables, LocalOp, lookup


class EngineState(NamedTuple):
    dir: dr.DirectoryState
    agent: ag.AgentState
    ch_req: tp.Channel     # remote -> home, coherence requests
    ch_resp: tp.Channel    # home -> remote, responses
    ch_hreq: tp.Channel    # home -> remote, home-initiated downgrades
    ch_hresp: tp.Channel   # remote -> home, downgrade replies
    hreq_pending: jnp.ndarray   # [L] int8: home request awaiting reply
    want_read: jnp.ndarray      # [L] bool: home-side read outstanding
    want_write: jnp.ndarray     # [L] bool: home-side write outstanding
    want_wval: jnp.ndarray      # [L, B]
    msg_count: jnp.ndarray      # [16] int32: delivered messages by type
    payload_msgs: jnp.ndarray   # [] int64: messages that carried data
    step_no: jnp.ndarray        # [] int32


class StepOutput(NamedTuple):
    load_done: jnp.ndarray    # [L] bool — a LOAD retired this step
    load_val: jnp.ndarray     # [L, B]
    hread_done: jnp.ndarray   # [L] bool — a home-side read retired
    hread_val: jnp.ndarray    # [L, B]
    accepted: jnp.ndarray     # [L] bool — this step's remote ops accepted


@functools.lru_cache(maxsize=None)
def _jitted_step(moesi: bool, stateless: bool):
    """One compiled step per (mode, stateless) pair, SHARED across Engine
    instances — a fresh ``jax.jit(partial(...))`` per instance would carry
    its own trace cache and recompile for every store/test constructed."""
    tables = FULL if moesi else MINIMAL
    return jax.jit(functools.partial(step, tables, stateless=stateless))


class Engine:
    """Convenience wrapper binding tables/config and jitting the step."""

    def __init__(self, backing: jnp.ndarray, moesi: bool = True,
                 stateless: bool = False,
                 delays: Optional[np.ndarray] = None,
                 credits: Optional[np.ndarray] = None):
        self.tables: DenseTables = FULL if moesi else MINIMAL
        self.stateless = stateless
        self.n_lines, self.block = backing.shape
        self.delays = jnp.asarray(
            delays if delays is not None else tp.DEFAULT_DELAYS)
        self.credits = jnp.asarray(
            credits if credits is not None else tp.DEFAULT_CREDITS)
        self._step = _jitted_step(moesi, stateless)
        self._backing = backing

    def init(self) -> EngineState:
        return make_engine_state(self._backing)

    def step(self, st: EngineState, op=None, op_val=None,
             want_read=None, want_write=None, wval=None
             ) -> Tuple[EngineState, StepOutput]:
        L, B = self.n_lines, self.block
        dt = st.dir.backing.dtype
        if op is None:
            op = jnp.zeros((L,), jnp.int8)
        if op_val is None:
            op_val = jnp.zeros((L, B), dt)
        if want_read is None:
            want_read = jnp.zeros((L,), bool)
        if want_write is None:
            want_write = jnp.zeros((L,), bool)
        if wval is None:
            wval = jnp.zeros((L, B), dt)
        return self._step(st, op, op_val, want_read, want_write, wval,
                          self.delays, self.credits)

    def drain(self, st: EngineState, max_steps: int = 64) -> EngineState:
        """Run empty steps until all transactions retire."""
        for _ in range(max_steps):
            if self.quiescent(st):
                break
            st, _ = self.step(st)
        return st

    def quiescent(self, st: EngineState) -> bool:
        # one fused expression -> a single device-to-host sync; drain
        # loops call this every round, so per-term syncs dominate wall-
        # clock otherwise.
        return not bool(busy_flag(st))

    def run_ops(self, st: EngineState, opv: jnp.ndarray, op_val: jnp.ndarray,
                max_rounds: int = 64):
        """Submit ``opv`` and drain to quiescence in ONE fused while_loop.

        The python-per-round drain this replaces paid a host sync plus a
        full dispatch per engine step; here the whole retire loop is a
        single device program.  Returns (state, done[L], vals[L,B],
        rounds, still_busy) — ``still_busy`` is the traced leftover-work
        flag the caller turns into the non-retirement error."""
        return _jitted_run_ops(self.tables.moesi, self.stateless)(
            st, opv, op_val, self.delays, self.credits,
            jnp.asarray(max_rounds, jnp.int32))


def busy_flag(st: EngineState) -> jnp.ndarray:
    """Traced scalar bool: any transaction, channel slot or home want is
    still in flight.  Shared by ``quiescent`` (host-side poll) and the
    fused drain loops (device-side while_loop condition)."""
    busy = ((st.agent.pending_req != 0).any()
            | (st.agent.pending_op != 0).any()
            | (st.hreq_pending != 0).any()
            | st.want_read.any() | st.want_write.any())
    for ch in (st.ch_req, st.ch_resp, st.ch_hreq, st.ch_hresp):
        busy = busy | (ch.msg != 0).any()
    return busy


@functools.lru_cache(maxsize=None)
def _jitted_run_ops(moesi: bool, stateless: bool):
    """One fused submit-and-drain program per (mode, stateless) pair,
    shared across Engine instances exactly like ``_jitted_step``."""
    tables = FULL if moesi else MINIMAL
    step_fn = functools.partial(step, tables, stateless=stateless)

    def run(st, opv, vv, delays, credits, max_rounds):
        L, B = st.dir.backing.shape
        zb = jnp.zeros((L,), bool)
        zwv = jnp.zeros((L, B), st.dir.backing.dtype)

        def cond(c):
            st_, opv_, _, _, rounds = c
            return (opv_.any() | busy_flag(st_)) & (rounds < max_rounds)

        def body(c):
            st_, opv_, done, vals, rounds = c
            st_, out = step_fn(st_, opv_, vv, zb, zb, zwv, delays, credits)
            opv_ = jnp.where(out.accepted, 0, opv_).astype(jnp.int8)
            done = done | out.load_done
            vals = jnp.where(out.load_done[:, None], out.load_val, vals)
            return (st_, opv_, done, vals, rounds + 1)

        init = (st, opv, zb, jnp.zeros((L, B), st.dir.backing.dtype),
                jnp.zeros((), jnp.int32))
        st, opv, done, vals, rounds = jax.lax.while_loop(cond, body, init)
        return st, done, vals, rounds, opv.any() | busy_flag(st)

    return jax.jit(run)


def make_engine_state(backing: jnp.ndarray) -> EngineState:
    L, B = backing.shape
    mk = lambda: tp.make_channel(L, B, backing.dtype)
    return EngineState(
        dir=dr.make_directory(backing),
        agent=ag.make_agent(L, B, backing.dtype),
        ch_req=mk(), ch_resp=mk(), ch_hreq=mk(), ch_hresp=mk(),
        hreq_pending=jnp.zeros((L,), jnp.int8),
        want_read=jnp.zeros((L,), bool),
        want_write=jnp.zeros((L,), bool),
        want_wval=jnp.zeros((L, B), backing.dtype),
        msg_count=jnp.zeros((16,), jnp.int32),
        payload_msgs=jnp.zeros((), jnp.int32),
        step_no=jnp.zeros((), jnp.int32),
    )


def _count(msg_count, payload_msgs, mask, msg, has_payload,
           backend: str = "xla"):
    """Accumulate delivered-message counts by type.

    One-hot compare + reduce instead of a scatter-add: XLA:CPU lowers
    scatter to a serial per-element loop, which at ``[R, L]`` sizes made
    the message counters ~45% of the whole N-remote step — the dense
    compare vectorizes and counts identically.  ``backend="pallas"``
    routes the fold through the ``kernels.coherency_step.count_fold``
    kernel (bit-identical integer arithmetic)."""
    with jax.named_scope("eci.counters"):
        if backend == "pallas":
            from ..kernels import ops as _kops
            delta, pay = _kops.count_fold(mask, msg, has_payload)
            return msg_count + delta, payload_msgs + pay
        eq = msg.astype(jnp.int32)[..., None] == jnp.arange(16)
        axes = tuple(range(eq.ndim - 1))
        msg_count = msg_count + (eq & mask[..., None]).sum(axes)
        payload_msgs = payload_msgs + (mask & has_payload).sum()
        return msg_count, payload_msgs


def stall_unready_ops(tables: DenseTables, ch_req, eff_op: jnp.ndarray,
                      remote_state: jnp.ndarray, op_val: jnp.ndarray,
                      credits: jnp.ndarray) -> jnp.ndarray:
    """Defer local ops whose outgoing message the transport cannot take.

    Dry-runs the submission (slot free + VC credit, via ``tp.submit``
    itself) and masks non-accepted ops to NOP so the caller retries them.
    Without this, a dirty eviction would apply its M->I hit-transition at
    the agent and then silently DROP the VOL_DOWNGRADE_I payload when the
    VC is out of credit.  The surviving emission set is a subset of the
    dry-run's candidates, so per-VC ranks can only shrink and the real
    submit accepts everything that emits.  Shared by both engines (the
    N-remote engine vmaps it over the remote axis).
    """
    o = eff_op.astype(jnp.int32)
    rs = remote_state.astype(jnp.int32)
    req_of = lookup(tables.loc_request, o, rs).astype(jnp.int8)
    would_emit = req_of != jnp.int8(int(MsgType.NOP))
    _, acc_pre = tp.submit(ch_req, tp.CLASS_REMOTE_REQ, would_emit, req_of,
                           jnp.zeros(would_emit.shape, bool), op_val,
                           credits)
    return jnp.where(would_emit & ~acc_pre, jnp.int8(int(LocalOp.NOP)),
                     eff_op)


def step(tables: DenseTables, st: EngineState,
         op: jnp.ndarray, op_val: jnp.ndarray,
         want_read: jnp.ndarray, want_write: jnp.ndarray,
         wval: jnp.ndarray, delays: jnp.ndarray, credits: jnp.ndarray,
         stateless: bool = False) -> Tuple[EngineState, StepOutput]:
    """One engine step.  See module docstring for the phase order."""
    nop = jnp.int8(int(MsgType.NOP))
    L, B = st.dir.backing.shape
    msg_count, payload_msgs = st.msg_count, st.payload_msgs

    # accumulate new home-side wants.
    want_read = st.want_read | want_read
    want_write = st.want_write | want_write
    wv = jnp.where((want_write & ~st.want_write)[:, None], wval,
                   st.want_wval)

    # ---- 1. time advances on all channels --------------------------------
    ch_req, ch_resp = tp.tick(st.ch_req), tp.tick(st.ch_resp)
    ch_hreq, ch_hresp = tp.tick(st.ch_hreq), tp.tick(st.ch_hresp)

    # ---- 2. deliver remote requests at the home directory ----------------
    ch_req_in = ch_req
    ch_req, arrived = tp.deliver(ch_req, tp.CLASS_REMOTE_REQ, delays)
    dstate, resp, resp_dirty, resp_pay = dr.process(
        tables, st.dir, arrived, ch_req_in.msg, ch_req_in.dirty,
        ch_req_in.payload, stateless=stateless)
    msg_count, payload_msgs = _count(msg_count, payload_msgs, arrived,
                                     ch_req_in.msg, ch_req_in.dirty)
    # responses sink unconditionally (deadlock-freedom argument).
    send_resp = resp != nop
    ch_resp, acc = tp.submit(ch_resp, tp.CLASS_HOME_RESP, send_resp, resp,
                             resp_dirty, resp_pay, credits, unbounded=True)
    msg_count, payload_msgs = _count(
        msg_count, payload_msgs, send_resp,
        resp, (resp == int(MsgType.RESP_DATA))
        | (resp == int(MsgType.RESP_DATA_DIRTY)))

    # ---- 3. deliver responses at the remote agent ------------------------
    ch_resp_in = ch_resp
    ch_resp, r_arr = tp.deliver(ch_resp, tp.CLASS_HOME_RESP, delays)
    was_load = st.agent.pending_op == int(LocalOp.LOAD)
    astate, _nack = ag.on_response(tables, st.agent, r_arr, ch_resp_in.msg,
                                   ch_resp_in.payload)
    load_done = r_arr & was_load & ~_nack
    load_val = jnp.where(load_done[:, None], astate.cache, 0)

    # ---- 4. deliver home-initiated downgrades at the remote --------------
    ch_hreq_in = ch_hreq
    ch_hreq, h_arr = tp.deliver(ch_hreq, tp.CLASS_HOME_REQ, delays)
    astate, hresp, hresp_dirty, hresp_pay = ag.on_home_msg(
        tables, astate, h_arr, ch_hreq_in.msg)
    msg_count, payload_msgs = _count(msg_count, payload_msgs, h_arr,
                                     ch_hreq_in.msg, jnp.zeros((L,), bool))
    send_h = hresp != nop
    ch_hresp, _ = tp.submit(ch_hresp, tp.CLASS_REMOTE_RESP, send_h, hresp,
                            hresp_dirty, hresp_pay, credits,
                            unbounded=True)
    msg_count, payload_msgs = _count(msg_count, payload_msgs, send_h, hresp,
                                     hresp_dirty)

    # ---- 5. deliver downgrade replies at the home ------------------------
    ch_hresp_in = ch_hresp
    ch_hresp, hr_arr = tp.deliver(ch_hresp, tp.CLASS_REMOTE_RESP, delays)
    # the transaction layer matches the reply to the original home request:
    dstate, _, _, _ = dr.process(
        tables, dstate, hr_arr, st.hreq_pending, ch_hresp_in.dirty,
        ch_hresp_in.payload, stateless=stateless)
    hreq_pending = jnp.where(hr_arr, nop, st.hreq_pending)

    # ---- 6. remote submits local ops (fresh + parked retries) ------------
    # Lines with a home-initiated downgrade in flight are LOCKED for new
    # remote transactions (the directory serializes conflicting requests;
    # per-line mutual exclusion is the transaction-layer race handling).
    locked = (hreq_pending != nop) | (ch_hreq.msg != nop)
    parked = (astate.pending_op != int(LocalOp.NOP)) & \
             (astate.pending_req == nop)
    eff_op = jnp.where(parked, astate.pending_op, op)
    eff_op = jnp.where(locked, jnp.int8(int(LocalOp.NOP)), eff_op)
    eff_op = stall_unready_ops(tables, ch_req, eff_op, astate.remote_state,
                               op_val, credits)
    eff_val = jnp.where(parked[:, None], astate.pending_val, op_val)
    astate2, accepted, emit, req_dirty, req_pay = ag.submit(
        tables, astate, eff_op, eff_val)
    send_req = emit != nop
    ch_req, acc_req = tp.submit(ch_req, tp.CLASS_REMOTE_REQ, send_req, emit,
                                req_dirty, req_pay, credits)
    # belt-and-braces: the dry-run guarantees acceptance, but revert the
    # MSHR of any refused line so a miss retries rather than hangs.
    refused = send_req & ~acc_req
    astate2 = astate2._replace(
        pending_req=jnp.where(refused, nop, astate2.pending_req))
    # load hits retire immediately.
    o = eff_op.astype(jnp.int32)
    rs = astate.remote_state.astype(jnp.int32)
    hit = lookup(tables.loc_hit, o, rs)
    load_hit = accepted & hit & (o == int(LocalOp.LOAD))
    load_done = load_done | load_hit
    load_val = jnp.where(load_hit[:, None], astate2.cache, load_val)

    # ---- 7. home-side accesses -------------------------------------------
    # The home only initiates a downgrade on a line with no remote
    # transaction anywhere in flight (per-line serialization, see step 6).
    remote_busy = (astate2.pending_req != nop) | \
                  (astate2.pending_op != int(LocalOp.NOP)) | \
                  (ch_req.msg != nop) | (ch_resp.msg != nop)
    idle_home = (hreq_pending == nop) & ~remote_busy
    need = dr.needed_downgrade(dstate, want_read & idle_home,
                               want_write & idle_home)
    # no downgrade needed -> the access retires now.
    ready = idle_home & (need == nop) & (want_read | want_write)
    hread_done = ready & want_read
    hread_val = jnp.where(hread_done[:, None], dr.home_read_value(dstate), 0)
    dstate = dr.home_apply_write(dstate, ready & want_write, wv)
    want_read2 = want_read & ~ready
    want_write2 = want_write & ~ready
    # downgrade needed -> emit on the home-request VC.
    send_hreq = idle_home & (need != nop)
    ch_hreq, acc_h = tp.submit(ch_hreq, tp.CLASS_HOME_REQ, send_hreq, need,
                               jnp.zeros((L,), bool), dstate.home_buf,
                               credits)
    hreq_pending = jnp.where(acc_h, need, hreq_pending)

    new = EngineState(
        dir=dstate, agent=astate2,
        ch_req=ch_req, ch_resp=ch_resp, ch_hreq=ch_hreq, ch_hresp=ch_hresp,
        hreq_pending=hreq_pending,
        want_read=want_read2, want_write=want_write2, want_wval=wv,
        msg_count=msg_count, payload_msgs=payload_msgs,
        step_no=st.step_no + 1,
    )
    # the caller's op was taken only where it (not a parked retry) ran.
    caller_taken = accepted & ~parked
    return new, StepOutput(load_done, load_val, hread_done, hread_val,
                           caller_taken)
