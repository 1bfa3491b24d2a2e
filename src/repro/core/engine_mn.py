"""The vectorized N-remote coherency engine (paper §4.1, R <= 64).

One home (sharer-vector directory, ``core.directory_mn``) — or ``H``
address-interleaved homes (``n_homes``, the multi-home fold below) — plus
``R`` caching remotes, each a full 4-state agent (``core.agent``) laid
over
one contiguous ``[R, L]`` slab — the per-remote virtual channels and MSHRs
are flat ``transport.Channel`` arrays with a leading remote axis, operated
on directly by the batch-polymorphic transport/agent primitives (no
``vmap`` wrappers: the traced program is one batched op per phase, so
trace/compile cost does not grow with per-remote structure and the step is
a fixed-op-count program whose arrays scale with R).  The whole step is
one fused ``jit`` program; python appears only in the drain loop, exactly
as in the 2-node engine.

The remote-count ceiling is the EWF node-id field: 6 bits since EWF v2
(``core.messages``), i.e. up to 64 caching remotes per home.

Transaction discipline (the "intermediate states" of a real directory):

* the home parks ONE request per line (``txn_msg``/``txn_node``), chosen
  among competing ready requests AND the home's own pending accesses
  (arbitration participant R, parked as the ``HOME_TXN`` sentinel) by a
  per-line ROTATING priority pointer (``arb_rr``, advanced past each
  winner — starvation-free under the sustained same-line traffic of
  ``repro.traffic``, for remotes and home alike), fans out one
  ``HOME_DOWNGRADE_*`` per conflicting sharer (the N-node message cost
  the paper's 2-node subsetting avoids), and grants once every reply has
  arrived and no voluntary downgrade is still in flight on the line;
* per-remote per-line channel slots serialize each remote's traffic, so a
  voluntary eviction always reaches the home before the same remote's next
  request — the ordering that keeps the race handling finite;
* crossings (a recall passing an eviction) resolve through the reply-race
  rows of the remote table plus view-aware absorption at the home
  (``directory_mn.absorb``), NACK+retry for invalidated upgrades.

``tests/test_engine_mn.py`` bisimulates this engine against the atomic
oracle ``core.multinode.MultiNodeRef`` for R in {2, 3, 4} (fast tier) and
R in {8, 16} (slow tier) in both MESI and MOESI modes.

The N-remote envelope excludes DEMOTE (transition 7) — the op set of the
oracle — which is a sound subset under requirement 5: the workload
guarantees ``VOL_DOWNGRADE_S`` is never generated, so the home need not
support it.
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import agent as ag
from . import directory_mn as dmn
from . import transport as tp
from .engine import _count
from .messages import MAX_NODE, MsgType
from .protocol import (ENHANCED_MESI, FULL_MOESI, DenseTables,
                       DenseTablesMN, LocalOp, MnAbsorb, ProtocolSubset,
                       bake_mn, lookup, mn_tables)
from .states import RemoteView

#: Remote-count ceiling, DERIVED from the EWF node-id field width — widening
#: the wire format (core.messages) widens the engine with it.
MAX_REMOTES = MAX_NODE + 1

#: ``txn_msg`` sentinel marking a line whose transaction slot is held by the
#: HOME itself: home-side accesses (``want_read``/``want_write``) compete in
#: the same rotating ``arb_rr`` arbitration as remote requests (participant
#: id R), so a home access bounded-waits under sustained streaming instead
#: of waiting for the line to drain — the ROADMAP starvation open item.
#: Outside the MsgType value range, so it can never collide with a parked
#: request.
HOME_TXN = 100

#: Step-kernel backends: "xla" is the original jnp hot path (the default —
#: every committed baseline and bisimulation is pinned against it);
#: "pallas" lowers the step's inner plane (credit ranking, arbitration
#: winner select, counter folds) through ``repro.kernels.coherency_step``
#: — bit-identical integer arithmetic, interpret mode on CPU, real Mosaic
#: lowering on TPU.  ``REPRO_KERNEL_BACKEND`` selects the default.
KERNEL_BACKENDS = ("xla", "pallas")


def resolve_kernel_backend(kernel_backend: str = "") -> str:
    """"" -> the ``REPRO_KERNEL_BACKEND`` env var -> "xla"."""
    kb = kernel_backend or os.environ.get("REPRO_KERNEL_BACKEND", "") \
        or "xla"
    if kb not in KERNEL_BACKENDS:
        raise ValueError(f"kernel_backend must be one of "
                         f"{KERNEL_BACKENDS}, got '{kb}'")
    return kb


# ---------------------------------------------------------------------------
# Multi-home fold: the [R, L] <-> [H, R, L/H] layout change.
#
# ``multinode.home_of`` interleaves line ownership by address
# (``line % H``), so the home-major layout is a pure reshape of the line
# axis: global line ``l = q*H + h`` lands at ``[h, ..., q]``.  Every
# transport/agent/directory primitive is polymorphic over leading batch
# axes, so the SAME step body runs the folded layout — one batched
# program, H home slices, compile time ~flat in H — and each home slice
# carries its own ``arb_rr``/transaction/MSHR plane and VC credit pools
# for free.  ``H == 1`` skips the fold entirely (bit-identical to the
# single-home engine).
# ---------------------------------------------------------------------------


def _f_l(x, H):       # [L, ...tail] per-line home-state style arrays
    """[L] -> [H, L/H] (or [L, B] -> [H, L/H, B])."""
    return jnp.moveaxis(x.reshape((x.shape[0] // H, H) + x.shape[1:]),
                        1, 0)


def _u_l(x):
    """Inverse of ``_f_l``: [H, L/H, ...] -> [L, ...]."""
    m = jnp.moveaxis(x, 0, 1)
    return m.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])


def _f_rl(x, H):
    """[R, L] -> [H, R, L/H] (or [R, L, B] -> [H, R, L/H, B])."""
    r, l = x.shape[:2]
    return jnp.moveaxis(x.reshape((r, l // H, H) + x.shape[2:]), 2, 0)


def _u_rl(x):
    """Inverse of ``_f_rl``: [H, R, L/H, ...] -> [R, L, ...]."""
    m = jnp.moveaxis(x, 0, 2)
    return m.reshape((x.shape[1], x.shape[2] * x.shape[0]) + x.shape[3:])


def _fold_state_mn(st: EngineMNState, H: int) -> EngineMNState:
    """Flat [R, L] engine state -> home-major [H, R, L/H] layout.

    The agents' per-remote tallies (``illegal``/``hits``/``misses``,
    shape [R]) have no line axis to fold; the folded state carries fresh
    [H, R] zeros and ``_unfold_state_mn`` adds the per-home deltas back
    onto the flat totals."""
    chf = lambda ch: tp.Channel(*(_f_rl(a, H) for a in ch))
    zr = jnp.zeros((H,) + st.agents.illegal.shape,
                   st.agents.illegal.dtype)
    return EngineMNState(
        dir=st.dir._replace(
            home_state=_f_l(st.dir.home_state, H),
            view=_f_rl(st.dir.view, H),
            backing=_f_l(st.dir.backing, H),
            home_buf=_f_l(st.dir.home_buf, H)),
        agents=st.agents._replace(
            remote_state=_f_rl(st.agents.remote_state, H),
            cache=_f_rl(st.agents.cache, H),
            pending_req=_f_rl(st.agents.pending_req, H),
            pending_op=_f_rl(st.agents.pending_op, H),
            pending_val=_f_rl(st.agents.pending_val, H),
            illegal=zr, hits=zr, misses=zr),
        ch_req=chf(st.ch_req), ch_resp=chf(st.ch_resp),
        ch_hreq=chf(st.ch_hreq), ch_hresp=chf(st.ch_hresp),
        hreq_pending=_f_rl(st.hreq_pending, H),
        txn_msg=_f_l(st.txn_msg, H),
        txn_node=_f_l(st.txn_node, H),
        arb_rr=_f_l(st.arb_rr, H),
        want_read=_f_l(st.want_read, H),
        want_write=_f_l(st.want_write, H),
        want_wval=_f_l(st.want_wval, H),
        msg_count=st.msg_count, payload_msgs=st.payload_msgs,
        step_no=st.step_no,
    )


def _unfold_state_mn(st: EngineMNState, flat: EngineMNState
                     ) -> EngineMNState:
    """Home-major [H, R, L/H] state -> flat [R, L]; ``flat`` supplies the
    pre-fold per-remote tally bases the folded zeros started from."""
    chu = lambda ch: tp.Channel(*(_u_rl(a) for a in ch))
    return EngineMNState(
        dir=st.dir._replace(
            home_state=_u_l(st.dir.home_state),
            view=_u_rl(st.dir.view),
            backing=_u_l(st.dir.backing),
            home_buf=_u_l(st.dir.home_buf)),
        agents=st.agents._replace(
            remote_state=_u_rl(st.agents.remote_state),
            cache=_u_rl(st.agents.cache),
            pending_req=_u_rl(st.agents.pending_req),
            pending_op=_u_rl(st.agents.pending_op),
            pending_val=_u_rl(st.agents.pending_val),
            illegal=flat.agents.illegal + st.agents.illegal.sum(axis=0),
            hits=flat.agents.hits + st.agents.hits.sum(axis=0),
            misses=flat.agents.misses + st.agents.misses.sum(axis=0)),
        ch_req=chu(st.ch_req), ch_resp=chu(st.ch_resp),
        ch_hreq=chu(st.ch_hreq), ch_hresp=chu(st.ch_hresp),
        hreq_pending=_u_rl(st.hreq_pending),
        txn_msg=_u_l(st.txn_msg),
        txn_node=_u_l(st.txn_node),
        arb_rr=_u_l(st.arb_rr),
        want_read=_u_l(st.want_read),
        want_write=_u_l(st.want_write),
        want_wval=_u_l(st.want_wval),
        msg_count=st.msg_count, payload_msgs=st.payload_msgs,
        step_no=st.step_no,
    )


class EngineMNState(NamedTuple):
    dir: dmn.DirectoryMNState
    agents: ag.AgentState        # every field has a leading [R] axis
    ch_req: tp.Channel           # [R, L] remote -> home requests + evictions
    ch_resp: tp.Channel          # [R, L] home -> remote grant responses
    ch_hreq: tp.Channel          # [R, L] home -> remote downgrades (fan-out)
    ch_hresp: tp.Channel         # [R, L] remote -> home downgrade replies
    hreq_pending: jnp.ndarray    # [R, L] int8: outstanding HOME_DOWNGRADE_*
    #                              (packed: [2, L, W] uint32 — plane 0 =
    #                              HD_S pending, plane 1 = HD_I pending)
    txn_msg: jnp.ndarray         # [L] int8: parked request type (NOP = none)
    txn_node: jnp.ndarray        # [L] int32: parked requester id
    arb_rr: jnp.ndarray          # [L] int32: rotating arbitration pointer
    want_read: jnp.ndarray       # [L] bool: home-side read outstanding
    want_write: jnp.ndarray      # [L] bool: home-side write outstanding
    want_wval: jnp.ndarray       # [L, B]
    msg_count: jnp.ndarray       # [16] int32: delivered messages by type
    payload_msgs: jnp.ndarray    # [] int32: messages that carried data
    step_no: jnp.ndarray         # [] int32


class StepMNOutput(NamedTuple):
    load_done: jnp.ndarray       # [R, L] bool — a LOAD retired this step
    load_val: jnp.ndarray        # [R, L, B]
    hread_done: jnp.ndarray      # [L] bool
    hread_val: jnp.ndarray       # [L, B]
    accepted: jnp.ndarray        # [R, L] bool — caller ops taken this step


class StepEvents(NamedTuple):
    """Wire events of ONE engine step, in delivery order — the in-scan
    observability feed (``traffic.observe``).

    The five sites are exactly the step's ``_count`` sites, in step-phase
    order (hresp arrivals, voluntary downgrades, request acceptance, grant
    issue, home-downgrade delivery) — the per-line serialization the NFA
    specs check and the EWF capture records.  Per-remote sites are
    ``[R, L]``; the home-side sites (one transaction per line) are
    ``[L]``.  Under the multi-home fold the events are unfolded back to
    flat global-line indexing, like every other step output.
    """

    hresp_arr: jnp.ndarray    # [R, L] bool — downgrade replies reaching home
    hresp_msg: jnp.ndarray    # [R, L] int8
    hresp_dirty: jnp.ndarray  # [R, L] bool
    vol_arr: jnp.ndarray      # [R, L] bool — voluntary downgrades absorbed
    vol_msg: jnp.ndarray      # [R, L] int8
    vol_dirty: jnp.ndarray    # [R, L] bool
    req_acc: jnp.ndarray      # [L] bool — remote request parked (wins arb)
    req_msg: jnp.ndarray      # [L] int8
    req_node: jnp.ndarray     # [L] int32
    grant: jnp.ndarray        # [L] bool — grant response issued
    grant_msg: jnp.ndarray    # [L] int8
    grant_node: jnp.ndarray   # [L] int32
    grant_pay: jnp.ndarray    # [L] bool — the grant carries line data
    hd_arr: jnp.ndarray       # [R, L] bool — HOME_DOWNGRADE_* delivered
    hd_msg: jnp.ndarray       # [R, L] int8


def make_engine_mn_state(backing: jnp.ndarray, n_remotes: int,
                         packed: bool = False) -> EngineMNState:
    L, B = backing.shape
    R = n_remotes

    def mk():
        ch = tp.make_channel(L, B, backing.dtype)
        return tp.Channel(*(jnp.broadcast_to(a, (R,) + a.shape) for a in ch))

    agent = ag.make_agent(L, B, backing.dtype)
    agents = ag.AgentState(*(jnp.broadcast_to(a, (R,) + a.shape)
                             for a in agent))
    # packed: directory view and the home-downgrade MSHR mask live as
    # [2, L, W] uint32 word planes (hreq_pending plane 0 = HD_S pending,
    # plane 1 = HD_I pending) instead of dense [R, L] int8.
    hreq = (jnp.zeros((2, L, dmn.n_words(R)), jnp.uint32) if packed
            else jnp.zeros((R, L), jnp.int8))
    return EngineMNState(
        dir=dmn.make_directory_mn(backing, R, packed=packed),
        agents=agents,
        ch_req=mk(), ch_resp=mk(), ch_hreq=mk(), ch_hresp=mk(),
        hreq_pending=hreq,
        txn_msg=jnp.zeros((L,), jnp.int8),
        txn_node=jnp.zeros((L,), jnp.int32),
        arb_rr=jnp.zeros((L,), jnp.int32),
        want_read=jnp.zeros((L,), bool),
        want_write=jnp.zeros((L,), bool),
        want_wval=jnp.zeros((L, B), backing.dtype),
        msg_count=jnp.zeros((16,), jnp.int32),
        payload_msgs=jnp.zeros((), jnp.int32),
        step_no=jnp.zeros((), jnp.int32),
    )


def _ready(ch: tp.Channel, delay_l: jnp.ndarray) -> jnp.ndarray:
    """[R, L] mask of in-flight messages whose VC delay has elapsed.

    The ``transport.deliver`` precondition, split out because request
    arbitration (step 4) must pop only the WINNING slot per line — every
    other channel uses the batched ``deliver`` directly.  ``delay_l`` is
    the caller's hoisted per-line delay gather for the channel's class."""
    with jax.named_scope("eci.transport"):
        return (ch.msg != int(MsgType.NOP)) & (ch.age >= delay_l[None, :])


def _pop(ch: tp.Channel, mask: jnp.ndarray) -> tp.Channel:
    """Free the slots in ``mask``; fields are read from the input channel."""
    with jax.named_scope("eci.transport"):
        return ch._replace(msg=jnp.where(mask, jnp.int8(int(MsgType.NOP)),
                                         ch.msg))


def step_mn(tables: DenseTables, tables_mn: DenseTablesMN,
            st: EngineMNState, op: jnp.ndarray, op_val: jnp.ndarray,
            want_read: jnp.ndarray, want_write: jnp.ndarray,
            wval: jnp.ndarray, delays: jnp.ndarray, credits: jnp.ndarray,
            hreq_shared: bool = False, n_homes: int = 1, home_bw: int = 0,
            emit_events: bool = False, kernel_backend: str = "xla",
            home_group=None, home_bw_t=None):
    """One fused engine step over all remotes and lines.

    PROTOCOL-PARAMETRIC: ``tables_mn`` is baked from a ``ProtocolSubset``
    (``protocol.bake_mn``) — local ops outside the subset are masked to
    NOP (defense in depth; the public APIs reject them loudly via
    ``check_workload`` first), requests outside ``remote_may_send`` are
    illegal at the directory, and a ``stateless_home`` subset's directory
    records nothing per line.  ``hreq_shared`` switches the home's fan-out
    submission to SHARED credit accounting (one budget across all R rows
    instead of per-row pools — the ROADMAP shared-credit link model).

    MULTI-HOME (``n_homes > 1``): line ownership interleaves across homes
    by address (``multinode.home_of``), and the step folds the flat
    ``[R, L]`` state into the home-major ``[H, R, L/H]`` layout at entry
    and unfolds at exit — the body in between is unchanged, because every
    transport/agent/directory primitive is polymorphic over leading batch
    axes.  Each home slice then owns its own ``arb_rr``/transaction/MSHR
    plane and VC credit pools; compile time stays ~flat in H (same traced
    program, one more batch axis).  ``home_bw > 0`` caps the NEW
    transactions each home parks per step (the directory-slice pipeline
    bandwidth — the single-directory ceiling ``bench_streaming``'s
    H-scaling curve measures); 0 means unbounded, and ``n_homes == 1``
    skips the fold entirely (bit-identical to the single-home engine).

    The transport/agent primitives are batch-polymorphic, so the ``[R, L]``
    channel/MSHR slabs are operated on directly — one batched op per phase
    regardless of R (the flat layout that lets this engine scale to
    ``MAX_REMOTES`` without per-remote traced structure).

    Single-pass discipline (the hot-path overhaul): per-VC delay gathers
    are hoisted once per class, response-class submits skip the credit
    ranking (they always sink), and the request path ranks credits exactly
    ONCE — the stall dry-run's acceptance is reused as the channel write
    mask, since the surviving emission set can only shrink between the
    dry-run and the write (same occupancy, smaller ranks).

    ``emit_events`` (static) additionally returns a ``StepEvents`` record
    of this step's wire events — the in-scan observability feed of
    ``traffic.observe``.  False (the default) leaves the returned tuple
    AND the traced program exactly as before: the event planes are values
    the step computes anyway, the flag only controls whether they are
    returned.

    ``kernel_backend`` (static) selects the inner-plane implementation:
    "xla" (default) keeps every jnp expression below bit-for-bit as
    committed; "pallas" routes the credit ranking, the arbitration winner
    select and the counter folds through ``repro.kernels.coherency_step``
    — same integer arithmetic, tested BIT-exact, interpret mode off-TPU.

    ``home_group``/``home_bw_t`` (TRACED int32 scalars, fleet use only —
    require ``n_homes == 1``/``home_bw == 0``) emulate the H-home fold's
    per-slice acceptance cap over the FLAT layout, so a vmapped fleet can
    sweep H without per-member fold shapes: VC parity follows the folded
    plane-local line index and new-transaction acceptance is capped per
    home slice of ``home_group`` interleaved lines.  ``home_group = 1``
    with ``home_bw_t = 0`` is bit-identical to the defaults."""
    if home_group is not None:
        assert n_homes == 1 and not home_bw, \
            "home_group emulation composes with the FLAT layout only " \
            "(static n_homes/home_bw must stay at their defaults)"
    if n_homes > 1:
        flat_in = st
        st = _fold_state_mn(st, n_homes)
        op, op_val = _f_rl(op, n_homes), _f_rl(op_val, n_homes)
        want_read = _f_l(want_read, n_homes)
        want_write = _f_l(want_write, n_homes)
        wval = _f_l(wval, n_homes)
    nop = jnp.int8(int(MsgType.NOP))
    # R/L come from the (always dense) agent plane: the directory/MSHR
    # slabs change layout under the bit-packed planes.  ``packed`` is a
    # trace-time constant — jit keys on avals, so the dense state compiles
    # the EXACT pre-packing program and the packed state its own.
    R, L = ag.plane_shape(st.agents)
    packed = st.hreq_pending.dtype == jnp.uint32

    def _pend_or(hp):
        # OR of the two pending word planes ([..., 2, L, W] -> [..., L, W]):
        # "any HOME_DOWNGRADE_* outstanding" per (remote bit, line).
        return hp[..., 0, :, :] | hp[..., 1, :, :]

    msg_count, payload_msgs = st.msg_count, st.payload_msgs
    lines = jnp.arange(L)
    rids = jnp.arange(R)
    # hoisted loop-invariant lookups: one delay gather per VC pair, shared
    # by every ready/deliver site on that class.  VC parity follows the
    # engine's OWN line axis: global line parity in the flat layout, but
    # plane-local parity (parity of ``l // H``) under the H-home fold —
    # the folded body sees only the reshaped axis.  The ``home_group``
    # emulation reproduces exactly that assignment over the flat layout
    # (``home_group = 1`` degenerates to global parity, bit-identical).
    par = (lines & 1) if home_group is None \
        else ((lines // home_group) & 1)
    with jax.named_scope("eci.transport"):
        dly_req = tp.vc_value(delays, par, tp.CLASS_REMOTE_REQ)
        dly_resp = tp.vc_value(delays, par, tp.CLASS_HOME_RESP)
        dly_hreq = tp.vc_value(delays, par, tp.CLASS_HOME_REQ)
        dly_hresp = tp.vc_value(delays, par, tp.CLASS_REMOTE_RESP)

    # accumulate new home-side wants.
    with jax.named_scope("eci.directory"):
        want_read = st.want_read | want_read
        want_write = st.want_write | want_write
        wv = jnp.where((want_write & ~st.want_write)[..., None], wval,
                       st.want_wval)

    # ---- 1. time advances on all channels --------------------------------
    ch_req, ch_resp = tp.tick(st.ch_req), tp.tick(st.ch_resp)
    ch_hreq, ch_hresp = tp.tick(st.ch_hreq), tp.tick(st.ch_hresp)

    # ---- 2. downgrade replies arrive at the home -------------------------
    ch_hresp_in = ch_hresp
    ch_hresp, hr_arr = tp.deliver(ch_hresp, tp.CLASS_REMOTE_RESP, delays,
                                  delay_l=dly_hresp)
    with jax.named_scope("eci.directory"):
        if packed:
            # plane 0 of the packed MSHR mask is "HOME_DOWNGRADE_S
            # pending"; absorb reads rep_kind only under hr_arr, and a reply
            # can only arrive for a sent (= pending) downgrade, so the bit
            # IS the kind.
            rep_kind = jnp.where(
                dmn.unpack_mask(st.hreq_pending[..., 0, :, :], R),
                jnp.int8(int(MnAbsorb.REPLY_S)),
                jnp.int8(int(MnAbsorb.REPLY_I)))
        else:
            rep_kind = jnp.where(
                st.hreq_pending == int(MsgType.HOME_DOWNGRADE_S),
                jnp.int8(int(MnAbsorb.REPLY_S)),
                jnp.int8(int(MnAbsorb.REPLY_I)))
        dstate = dmn.absorb(tables_mn, st.dir, hr_arr, rep_kind,
                            ch_hresp_in.dirty, ch_hresp_in.payload,
                            backend=kernel_backend)
        if packed:
            hreq_pending = st.hreq_pending & \
                ~dmn.pack_mask(hr_arr)[..., None, :, :]
        else:
            hreq_pending = jnp.where(hr_arr, nop, st.hreq_pending)
    msg_count, payload_msgs = _count(msg_count, payload_msgs, hr_arr,
                                     ch_hresp_in.msg, ch_hresp_in.dirty,
                                     backend=kernel_backend)

    # ---- 3. voluntary downgrades arrive at the home ----------------------
    ready_req = _ready(ch_req, dly_req)
    with jax.named_scope("eci.directory"):
        is_vol = (ch_req.msg == int(MsgType.VOL_DOWNGRADE_I)) | \
                 (ch_req.msg == int(MsgType.VOL_DOWNGRADE_S))
        pop_vol = ready_req & is_vol
        dstate = dmn.absorb(
            tables_mn, dstate, pop_vol,
            jnp.full(pop_vol.shape, int(MnAbsorb.VOL_I), jnp.int8),
            ch_req.dirty, ch_req.payload, backend=kernel_backend)
    msg_count, payload_msgs = _count(msg_count, payload_msgs, pop_vol,
                                     ch_req.msg, ch_req.dirty,
                                     backend=kernel_backend)
    # observability site 2: voluntary downgrades as absorbed (pre-pop).
    vol_msg, vol_dirty = ch_req.msg, ch_req.dirty

    # ---- 4. arbitration: remotes AND the home compete per free line ------
    with jax.named_scope("eci.arbitrate"):
        req_ready = ready_req & ~is_vol
        # a line is free for a new transaction only when no downgrade
        # round-trip is outstanding AND no grant response is still in
        # flight — otherwise a fan-out invalidation could cross the previous
        # requester's grant (the delivered response would resurrect a sharer
        # the directory just wrote off).  Per-line serialization, as in the
        # 2-node engine's step 6/7.
        resp_in_flight = tp.any_in_flight(ch_resp)
        if packed:
            pend_any = dmn.any_bits(_pend_or(hreq_pending), kernel_backend)
        else:
            pend_any = (hreq_pending != nop).any(axis=-2)
        line_free = (st.txn_msg == nop) & ~pend_any & ~resp_in_flight
        # The home is arbitration participant R: an outstanding want
        # competes for the line's transaction slot like any remote request,
        # so it bounded-waits under sustained streaming instead of waiting
        # for the line to drain (the pre-fix unbounded starvation).
        home_ready = want_read | want_write
        any_req = req_ready.any(axis=-2) | home_ready
        # Rotating priority (the ROADMAP starvation fix): the per-line
        # pointer ``arb_rr`` names the highest-priority participant; each
        # accepted request advances it PAST the winner, so a
        # persistently-ready participant climbs one rank per transaction and
        # wins within R grants — a bounded wait no fixed argmax order gives.
        # (Rotating by raw ``step_no`` is NOT enough: contended-line
        # transaction latencies can align with the rotation period and park
        # the same priority order at every free instant — the pointer
        # rotates per GRANT, which cannot alias.)
        ready_all = jnp.concatenate([req_ready, home_ready[..., None, :]],
                                    axis=-2)
        if kernel_backend == "pallas":
            from ..kernels import ops as _kops
            winner = _kops.arb_winner(ready_all, st.arb_rr)
        else:
            prio = (jnp.arange(R + 1)[:, None] - st.arb_rr[..., None, :]) \
                % (R + 1)
            winner = jnp.argmin(jnp.where(ready_all, prio, R + 1), axis=-2)
        accept_line = any_req & line_free
        if home_group is not None:
            # Fleet emulation of the folded per-home acceptance cap: lines
            # interleave across ``home_group`` homes by address
            # (``l % hg``), each home ranks ITS accepted lines in the folded
            # plane's
            # rotating order (plane position ``l // hg``, origin rotating by
            # step), and keeps the first ``home_bw_t``.  ``home_bw_t = 0``
            # disables the cap (rank < L+1 always holds).
            hg = home_group
            Lh = L // hg
            off = st.step_no % Lh
            h_of = lines % hg
            rot = (lines // hg - off) % Lh
            same = h_of[:, None] == h_of[None, :]
            earl = rot[None, :] < rot[:, None]
            rank = (accept_line[..., None, :] & same & earl).sum(-1)
            cap = jnp.where(home_bw_t > 0, home_bw_t, jnp.int32(L + 1))
            accept_line = accept_line & (rank < cap)
        elif home_bw:
            # Directory-slice pipeline bandwidth: each home parks at most
            # ``home_bw`` NEW transactions per step (in-flight ones proceed
            # unthrottled — this caps ACCEPTANCE, so it only delays, never
            # changes, the per-line serialization the bisimulation pins).
            # Priority rotates its origin line every step; under a fixed
            # cumsum order a saturated low line range would starve the
            # tail.
            off = st.step_no % L
            pos = (lines + off) % L
            rolled = jnp.take(accept_line, pos, axis=-1).astype(jnp.int32)
            rank = jnp.take(jnp.cumsum(rolled, axis=-1) - rolled,
                            (lines - off) % L, axis=-1)
            accept_line = accept_line & (rank < home_bw)
        home_win = accept_line & (winner == R)
        arb_rr = jnp.where(accept_line, (winner + 1) % (R + 1), st.arb_rr)
        win_node = jnp.minimum(winner, R - 1)
        win_msg = jnp.where(home_win, jnp.int8(HOME_TXN),
                            dmn._take_remote(ch_req.msg, win_node))
        pop_req = (accept_line & ~home_win)[..., None, :] & \
            (rids[:, None] == winner[..., None, :])
        ch_req = _pop(ch_req, pop_vol | (pop_req & req_ready))
        txn_msg = jnp.where(accept_line, win_msg, st.txn_msg)
        txn_node = jnp.where(accept_line, winner, st.txn_node)
    msg_count, payload_msgs = _count(
        msg_count, payload_msgs, accept_line & ~home_win, win_msg,
        jnp.zeros(accept_line.shape, bool), backend=kernel_backend)

    # ---- 5. fan-out: emit one HOME_DOWNGRADE_* per conflicting sharer ----
    with jax.named_scope("eci.directory"):
        active_txn = txn_msg != nop
        is_home_txn = txn_msg == HOME_TXN
        # the home's participant id R is clamped for view/table gathers;
        # every use is masked by ~is_home_txn (or by resp == NOP, which home
        # transactions never produce).
        node_c = jnp.minimum(txn_node, R - 1)
        # an UPGRADE whose requester was concurrently invalidated is doomed
        # to a NACK — suppress its fan-out so the new owner keeps the line.
        req_view_now = dmn.view_of(dstate, node_c)
        doomed = active_txn & (txn_msg == int(MsgType.REQ_UPGRADE)) & \
            (req_view_now != int(RemoteView.S))
        if packed:
            # fan-out sets as word planes: recall (HD_S) / invalidate
            # (HD_I) targets are one AND-NOT-hot each over the
            # presence/exclusive planes, then widened to the dense [R, L]
            # lane mask the (dense) transport submit needs.  The planes are
            # per-line disjoint, so the HD_S-wins combine below matches the
            # dense expression.
            ns_w, ni_w = dmn.needed_words(
                dstate, active_txn & ~doomed & ~is_home_txn, txn_msg,
                node_c, kernel_backend)
            nsh_w, nih_w = dmn.home_needed_words(
                dstate, want_read & is_home_txn, want_write & is_home_txn)
            iht = is_home_txn[..., None]
            need_s_w = jnp.where(iht, nsh_w, ns_w)
            need_i_w = jnp.where(iht, nih_w, ni_w)
            needed = jnp.where(
                dmn.unpack_mask(need_s_w, R),
                jnp.int8(int(MsgType.HOME_DOWNGRADE_S)),
                jnp.where(dmn.unpack_mask(need_i_w, R),
                          jnp.int8(int(MsgType.HOME_DOWNGRADE_I)), nop))
            send_h = (needed != nop) & \
                ~dmn.unpack_mask(_pend_or(hreq_pending), R)
        else:
            needed_r = dmn.needed_downgrades(
                dstate, active_txn & ~doomed & ~is_home_txn, txn_msg,
                node_c)
            # a parked HOME transaction fans out through the SAME
            # machinery: reads recall a dirty owner to S, writes invalidate
            # every sharer.
            needed_h = dmn.home_needed_downgrades(
                dstate, want_read & is_home_txn, want_write & is_home_txn)
            needed = jnp.where(is_home_txn[..., None, :], needed_h,
                               needed_r)
            send_h = (needed != nop) & (hreq_pending == nop)
        ch_hreq, acc_h = tp.submit(ch_hreq, tp.CLASS_HOME_REQ, send_h,
                                   needed, jnp.zeros(send_h.shape, bool),
                                   jnp.zeros_like(st.ch_hreq.payload),
                                   credits, shared=hreq_shared,
                                   backend=kernel_backend)
        if packed:
            # acc_h ⊆ send_h ⊆ pending-free, and every accepted lane sits
            # in exactly one of the two word planes — OR-in is the masked
            # store.
            acc_w = dmn.pack_mask(acc_h)
            hreq_pending = jnp.stack(
                [hreq_pending[..., 0, :, :] | (acc_w & need_s_w),
                 hreq_pending[..., 1, :, :] | (acc_w & need_i_w)], axis=-3)
        else:
            hreq_pending = jnp.where(acc_h, needed, hreq_pending)

    # ---- 6. grant parked requests whose preconditions now hold -----------
    with jax.named_scope("eci.directory"):
        in_flight_vol = ((ch_req.msg == int(MsgType.VOL_DOWNGRADE_I)) |
                         (ch_req.msg == int(MsgType.VOL_DOWNGRADE_S))
                         ).any(axis=-2)
        in_flight_h = tp.any_in_flight(ch_hreq) | tp.any_in_flight(ch_hresp)
        # `needed` must be EMPTY, not merely pending-free: a fan-out submission
        # refused for credit leaves hreq_pending == NOP with the sharer's view
        # intact — granting then would hand out exclusivity while the line is
        # still shared.  (Home transactions complete under the same guard.)
        if packed:
            complete = active_txn & \
                ~dmn.any_bits(need_s_w | need_i_w, kernel_backend) & \
                ~dmn.any_bits(_pend_or(hreq_pending), kernel_backend) & \
                ~in_flight_vol & ~in_flight_h
        else:
            complete = active_txn & ~(needed != nop).any(axis=-2) & \
                ~(hreq_pending != nop).any(axis=-2) & \
                ~in_flight_vol & ~in_flight_h
        complete_r = complete & ~is_home_txn
        dstate, resp, resp_pay = dmn.grant(tables_mn, dstate, complete_r,
                                           txn_msg, node_c)
        # a completed HOME transaction services the access in place: the read
        # serves the coherent line value, the write lands through the home
        # tables — no message leaves the home.
        complete_h = complete & is_home_txn
        hread_done = complete_h & want_read
        hread_val = jnp.where(hread_done[..., None], dmn.home_value(dstate), 0)
        dstate = dmn.home_apply_write(dstate, complete_h & want_write, wv)
        want_read2 = want_read & ~complete_h
        want_write2 = want_write & ~complete_h
        txn_msg = jnp.where(complete, nop, txn_msg)
        send_resp = (rids[:, None] == txn_node[..., None, :]) & \
            (resp != nop)[..., None, :]
        ch_resp, _ = tp.submit(ch_resp, tp.CLASS_HOME_RESP, send_resp,
                               jnp.broadcast_to(resp[..., None, :],
                                                send_resp.shape),
                               jnp.zeros(send_resp.shape, bool),
                               jnp.broadcast_to(resp_pay[..., None, :, :],
                                                send_resp.shape
                                                + resp_pay.shape[-1:]),
                               credits, unbounded=True)
        carries = (resp == int(MsgType.RESP_DATA)) | \
                  (resp == int(MsgType.RESP_DATA_DIRTY))
        msg_count, payload_msgs = _count(msg_count, payload_msgs,
                                         resp != nop, resp, carries,
                                         backend=kernel_backend)

    # ---- 7. grant responses arrive at the remotes ------------------------
    ch_resp_in = ch_resp
    ch_resp, r_arr = tp.deliver(ch_resp, tp.CLASS_HOME_RESP, delays,
                                delay_l=dly_resp)
    with jax.named_scope("eci.agents"):
        was_load = st.agents.pending_op == int(LocalOp.LOAD)
        agents, _nack = ag.on_response(tables, st.agents, r_arr,
                                       ch_resp_in.msg, ch_resp_in.payload,
                                       nack_holds=True)
        load_done = r_arr & was_load & ~_nack
        load_val = jnp.where(load_done[..., None], agents.cache, 0)

    # ---- 8. home-initiated downgrades arrive at the remotes --------------
    ch_hreq_in = ch_hreq
    ch_hreq, h_arr = tp.deliver(ch_hreq, tp.CLASS_HOME_REQ, delays,
                                delay_l=dly_hreq)
    with jax.named_scope("eci.agents"):
        agents, hresp, hresp_dirty, hresp_pay = ag.on_home_msg(
            tables, agents, h_arr, ch_hreq_in.msg)
    msg_count, payload_msgs = _count(msg_count, payload_msgs, h_arr,
                                     ch_hreq_in.msg,
                                     jnp.zeros(h_arr.shape, bool),
                                     backend=kernel_backend)
    ch_hresp, _ = tp.submit(ch_hresp, tp.CLASS_REMOTE_RESP, hresp != nop,
                            hresp, hresp_dirty, hresp_pay, credits,
                            unbounded=True)

    # ---- 9. remotes submit local ops (fresh + parked retries) ------------
    with jax.named_scope("eci.agents"):
        if packed:
            locked = dmn.unpack_mask(_pend_or(hreq_pending), R) | \
                (ch_hreq.msg != nop)
        else:
            locked = (hreq_pending != nop) | (ch_hreq.msg != nop)
        parked = (agents.pending_op != int(LocalOp.NOP)) & \
                 (agents.pending_req == nop)
        eff_op = jnp.where(parked, agents.pending_op, op)
        eff_op = jnp.where(locked, jnp.int8(int(LocalOp.NOP)), eff_op)
        # mask ops outside the subset's MN envelope (DEMOTE always — see the
        # module docstring — plus whatever the subset's guarantee excludes;
        # the public APIs reject such programs loudly BEFORE they get here).
        op_ok = lookup(tables_mn.op_ok, eff_op)
        eff_op = jnp.where(op_ok, eff_op, jnp.int8(int(LocalOp.NOP)))
        # An op that would emit a message stalls until the transport CAN take
        # it (slot + credit) — the dirty-eviction drop guard of
        # engine.stall_unready_ops, with the credit ranking computed ONCE: the
        # real emission set below is a subset of these candidates on unchanged
        # occupancy (ranks only shrink), so the dry-run verdict IS the final
        # acceptance and the channel write needs no second ranking.
        o = eff_op.astype(jnp.int32)
        rs = agents.remote_state.astype(jnp.int32)
        req_of = lookup(tables.loc_request, o, rs).astype(jnp.int8)
        would_emit = req_of != nop
        acc_pre = tp.credit_accept(ch_req, tp.CLASS_REMOTE_REQ,
                                   would_emit & (ch_req.msg == nop), credits,
                                   backend=kernel_backend)
        eff_op = jnp.where(would_emit & ~acc_pre, jnp.int8(int(LocalOp.NOP)),
                           eff_op)
        eff_val = jnp.where(parked[..., None], agents.pending_val, op_val)
        agents2, accepted, emit, req_dirty, req_pay = ag.submit(
            tables, agents, eff_op, eff_val)
        ch_req = tp.place(ch_req, emit != nop, emit, req_dirty, req_pay)
        # load hits retire immediately.
        o = eff_op.astype(jnp.int32)
        hit = lookup(tables.loc_hit, o, rs)
        load_hit = accepted & hit & (o == int(LocalOp.LOAD))
        load_done = load_done | load_hit
        load_val = jnp.where(load_hit[..., None], agents2.cache, load_val)

    new = EngineMNState(
        dir=dstate, agents=agents2,
        ch_req=ch_req, ch_resp=ch_resp, ch_hreq=ch_hreq, ch_hresp=ch_hresp,
        hreq_pending=hreq_pending, txn_msg=txn_msg, txn_node=txn_node,
        arb_rr=arb_rr,
        want_read=want_read2, want_write=want_write2, want_wval=wv,
        msg_count=msg_count, payload_msgs=payload_msgs,
        step_no=st.step_no + 1,
    )
    caller_taken = accepted & ~parked
    out = StepMNOutput(load_done, load_val, hread_done, hread_val,
                       caller_taken)
    ev = None
    if emit_events:
        ev = StepEvents(
            hresp_arr=hr_arr, hresp_msg=ch_hresp_in.msg,
            hresp_dirty=ch_hresp_in.dirty,
            vol_arr=pop_vol, vol_msg=vol_msg, vol_dirty=vol_dirty,
            req_acc=accept_line & ~home_win, req_msg=win_msg,
            req_node=win_node,
            grant=resp != nop, grant_msg=resp,
            grant_node=node_c, grant_pay=carries,
            hd_arr=h_arr, hd_msg=ch_hreq_in.msg)
    if n_homes > 1:
        new = _unfold_state_mn(new, flat_in)
        out = StepMNOutput(
            load_done=_u_rl(out.load_done), load_val=_u_rl(out.load_val),
            hread_done=_u_l(out.hread_done),
            hread_val=_u_l(out.hread_val),
            accepted=_u_rl(out.accepted))
        if emit_events:
            ev = StepEvents(
                hresp_arr=_u_rl(ev.hresp_arr),
                hresp_msg=_u_rl(ev.hresp_msg),
                hresp_dirty=_u_rl(ev.hresp_dirty),
                vol_arr=_u_rl(ev.vol_arr), vol_msg=_u_rl(ev.vol_msg),
                vol_dirty=_u_rl(ev.vol_dirty),
                req_acc=_u_l(ev.req_acc), req_msg=_u_l(ev.req_msg),
                req_node=_u_l(ev.req_node),
                grant=_u_l(ev.grant), grant_msg=_u_l(ev.grant_msg),
                grant_node=_u_l(ev.grant_node),
                grant_pay=_u_l(ev.grant_pay),
                hd_arr=_u_rl(ev.hd_arr), hd_msg=_u_rl(ev.hd_msg))
    if emit_events:
        return new, out, ev
    return new, out


def _jitted_step_mn(subset_name: str, hreq_shared: bool = False,
                    n_homes: int = 1, home_bw: int = 0,
                    kernel_backend: str = "xla"):
    """One compiled step per (protocol subset, credit model, home plan,
    kernel backend), shared across engine instances (shape changes
    retrace inside jax.jit's own cache).

    A plain normalization wrapper over the lru-cached impl, so the
    historical 4-argument call and the 5-argument call with the default
    backend land on the SAME cache entry (lru_cache keys on the raw call
    signature, which would otherwise split them).

    The incoming state is DONATED: the ``[R, L]`` channel/MSHR/directory
    slabs update in place instead of reallocating every step.  Callers must
    treat a stepped state as consumed (every in-repo driver rebinds)."""
    return _jitted_step_mn_impl(subset_name, hreq_shared, n_homes,
                                home_bw, kernel_backend)


@functools.lru_cache(maxsize=None)
def _jitted_step_mn_impl(subset_name: str, hreq_shared: bool,
                         n_homes: int, home_bw: int,
                         kernel_backend: str):
    tables_mn = mn_tables(subset_name)
    return jax.jit(functools.partial(step_mn, tables_mn.base, tables_mn,
                                     hreq_shared=hreq_shared,
                                     n_homes=n_homes, home_bw=home_bw,
                                     kernel_backend=kernel_backend),
                   donate_argnums=0)


def busy_flag_mn(st: EngineMNState) -> jnp.ndarray:
    """Traced scalar bool: any transaction, channel slot or home want is
    still in flight (device-side twin of ``EngineMN.quiescent``)."""
    busy = ((st.agents.pending_req != 0).any()
            | (st.agents.pending_op != 0).any()
            | (st.hreq_pending != 0).any()
            | (st.txn_msg != 0).any()
            | st.want_read.any() | st.want_write.any())
    for ch in (st.ch_req, st.ch_resp, st.ch_hreq, st.ch_hresp):
        busy = busy | (ch.msg != 0).any()
    return busy


@functools.lru_cache(maxsize=None)
def _jitted_run_ops_mn(subset_name: str, hreq_shared: bool = False,
                       n_homes: int = 1, home_bw: int = 0,
                       kernel_backend: str = "xla"):
    """One fused submit-and-drain program per (subset, credit model, home
    plan, kernel backend), shared across EngineMN instances like
    ``_jitted_step_mn``."""
    tables_mn = mn_tables(subset_name)
    step_fn = functools.partial(step_mn, tables_mn.base, tables_mn,
                                hreq_shared=hreq_shared,
                                n_homes=n_homes, home_bw=home_bw,
                                kernel_backend=kernel_backend)

    def run(st, opv, vv, delays, credits, max_rounds):
        L, B = st.dir.backing.shape
        zb = jnp.zeros((L,), bool)
        zwv = jnp.zeros((L, B), st.dir.backing.dtype)

        def cond(c):
            st_, opv_, _, _, rounds = c
            return (opv_.any() | busy_flag_mn(st_)) & (rounds < max_rounds)

        def body(c):
            st_, opv_, done, vals, rounds = c
            st_, out = step_fn(st_, opv_, vv, zb, zb, zwv, delays, credits)
            opv_ = jnp.where(out.accepted, 0, opv_).astype(jnp.int8)
            ld = out.load_done.any(axis=0)
            done = done | ld
            # one-hot over remotes (at most one acts per line per call).
            vals = jnp.where(ld[:, None], out.load_val.sum(axis=0), vals)
            return (st_, opv_, done, vals, rounds + 1)

        init = (st, opv, zb, jnp.zeros((L, B), st.dir.backing.dtype),
                jnp.zeros((), jnp.int32))
        st, opv, done, vals, rounds = jax.lax.while_loop(cond, body, init)
        return st, done, vals, rounds, opv.any() | busy_flag_mn(st)

    # the state is donated (in-place slab updates); CoherentStore rebinds.
    return jax.jit(run, donate_argnums=0)


class EngineMN:
    """Convenience wrapper binding subset/config and jitting the step.

    PROTOCOL-PARAMETRIC (§3.4): pass any ``ProtocolSubset`` — the engine
    runs the subset's baked tables, masks, and (for STATELESS) the
    no-per-line-state home.  ``moesi`` is kept as a convenience alias for
    the two full-protocol members (``moesi=True`` → FULL_MOESI, ``False``
    → ENHANCED_MESI); an explicit ``subset`` wins.

    ``shared_credits=True`` switches the home-request VC to a shared
    credit pool across all R rows — the link model under which the R-1
    invalidation fan-out on one line's VC pair can actually stall (see
    docs/traffic.md, "Shared-credit link model").

    MULTI-HOME (``n_homes > 1``): line ownership interleaves across homes
    by address (``multinode.home_of``) and the step runs the home-major
    ``[H, R, L/H]`` fold — each home gets its own arbitration/transaction/
    MSHR plane and credit pools (see docs/multinode.md, "Sharding the
    home").  ``home_bw`` caps new transactions accepted per home per step
    (0 = unbounded), modeling the directory-slice pipeline bandwidth.

    ``kernel_backend`` selects the step's inner-plane implementation
    ("xla" default / "pallas" — see ``KERNEL_BACKENDS``); "" defers to
    the ``REPRO_KERNEL_BACKEND`` environment variable, then "xla".  Both
    backends are BIT-identical (docs/perf.md, "Kernel backends").

    ``packed=True`` stores the directory view and the home-downgrade MSHR
    mask as ``[2, L, ceil(R/32)]`` uint32 word planes (presence/exclusive
    bits; HD_S/HD_I pending bits) instead of dense ``[R, L]`` int8 — the
    sharer reductions become word ops, cutting per-step directory memory
    traffic up to 32x at R=64 while staying bit-identical on counters,
    traces and oracle replay (docs/perf.md, "Packed directory planes").
    The layout is carried by the STATE's dtypes, so the jitted step needs
    no extra static argument and the dense default keeps the exact
    pre-packing cached program.
    """

    def __init__(self, backing: jnp.ndarray, n_remotes: int,
                 moesi: bool = True,
                 delays: Optional[np.ndarray] = None,
                 credits: Optional[np.ndarray] = None,
                 subset: Optional[ProtocolSubset] = None,
                 shared_credits: bool = False,
                 n_homes: int = 1, home_bw: int = 0,
                 kernel_backend: str = "", packed: bool = False):
        assert 1 <= n_remotes <= MAX_REMOTES, \
            f"EWF v2 carries 6-bit node ids (n_remotes={n_remotes})"
        self.n_remotes = n_remotes
        if subset is None:
            subset = FULL_MOESI if moesi else ENHANCED_MESI
        self.subset = subset
        self.moesi = subset.tables.moesi
        self.tables = subset.tables
        self.tables_mn = bake_mn(subset)
        self.shared_credits = shared_credits
        self.n_lines, self.block = backing.shape
        assert n_homes >= 1 and self.n_lines % n_homes == 0, \
            f"n_homes={n_homes} must divide n_lines={self.n_lines} " \
            f"(address-interleaved fold reshapes the line axis)"
        assert home_bw >= 0, \
            f"home_bw={home_bw} must be >= 0 (0 = unbounded acceptance)"
        self.n_homes = n_homes
        self.home_bw = home_bw
        self.kernel_backend = resolve_kernel_backend(kernel_backend)
        self.packed = bool(packed)
        self.delays = jnp.asarray(
            delays if delays is not None else tp.DEFAULT_DELAYS)
        self.credits = jnp.asarray(
            credits if credits is not None else tp.DEFAULT_CREDITS)
        self._step = _jitted_step_mn(subset.name, shared_credits,
                                     n_homes, home_bw,
                                     self.kernel_backend)
        self._backing = backing

    @classmethod
    def from_config(cls, cfg) -> "EngineMN":
        """Build from a ``traffic.config.EngineConfig``-shaped object —
        the single construction surface the CLI, smoke and bench share.

        Duck-typed on attribute names (``remotes``/``lines``/``block``/
        ``subset``/``moesi``/``credits``/``shared_credits``/``homes``/
        ``home_bw``) so core never imports the traffic package.
        ``subset`` is a ``SUBSETS`` name ("" lets ``moesi`` pick the full
        protocol); ``credits`` is a uniform per-VC override (0 = the
        transport default)."""
        from .protocol import SUBSETS
        subset = SUBSETS[cfg.subset] if cfg.subset else None
        credits = None
        if cfg.credits:
            credits = np.asarray([cfg.credits] * tp.N_VCS, np.int32)
        return cls(jnp.zeros((cfg.lines, cfg.block), jnp.float32),
                   n_remotes=cfg.remotes, moesi=cfg.moesi, subset=subset,
                   credits=credits, shared_credits=cfg.shared_credits,
                   n_homes=cfg.homes, home_bw=cfg.home_bw,
                   kernel_backend=getattr(cfg, "kernel_backend", ""),
                   packed=getattr(cfg, "packed", False))

    def init(self) -> EngineMNState:
        # fresh copy of the backing: the jitted hot paths DONATE the state,
        # so the first state's buffers must not alias the caller's array
        # (donation would delete it out from under a later init()).
        return make_engine_mn_state(jnp.array(self._backing),
                                    self.n_remotes, packed=self.packed)

    def step(self, st: EngineMNState, op=None, op_val=None,
             want_read=None, want_write=None, wval=None
             ) -> Tuple[EngineMNState, StepMNOutput]:
        R, L, B = self.n_remotes, self.n_lines, self.block
        dt = st.dir.backing.dtype
        if op is None:
            op = jnp.zeros((R, L), jnp.int8)
        if op_val is None:
            op_val = jnp.zeros((R, L, B), dt)
        if want_read is None:
            want_read = jnp.zeros((L,), bool)
        if want_write is None:
            want_write = jnp.zeros((L,), bool)
        if wval is None:
            wval = jnp.zeros((L, B), dt)
        return self._step(st, op, op_val, want_read, want_write, wval,
                          self.delays, self.credits)

    def drain(self, st: EngineMNState, max_steps: int = 128,
              strict: bool = True) -> EngineMNState:
        """Run empty steps until every transaction retires.

        Raises ``RuntimeError`` if the engine is still busy after
        ``max_steps`` — a contended R=64 line set can legitimately need
        more than the default budget, and silently returning a
        non-quiescent state poisons everything downstream (callers read
        values out of half-finished transactions).  ``strict=False``
        restores the old return-what-we-have behavior for callers that
        poll ``quiescent`` themselves."""
        for _ in range(max_steps):
            if self.quiescent(st):
                return st
            st, _ = self.step(st)
        if not self.quiescent(st) and strict:
            raise RuntimeError(
                f"EngineMN.drain: engine still busy after {max_steps} "
                f"steps (R={self.n_remotes}, L={self.n_lines}, "
                f"H={self.n_homes}) — raise max_steps or pass "
                f"strict=False to poll quiescent() yourself")
        return st

    def quiescent(self, st: EngineMNState) -> bool:
        # one fused expression -> a single device-to-host sync per call
        # (drain loops poll this every round).
        return not bool(busy_flag_mn(st))

    def run_ops(self, st: EngineMNState, opv: jnp.ndarray,
                op_val: jnp.ndarray, max_rounds: int = 64):
        """Submit ``opv`` [R, L] and drain to quiescence in ONE fused
        while_loop — see ``Engine.run_ops``.  Returns (state, done[L],
        vals[L,B], rounds, still_busy) with done/vals reduced over the
        remote axis (at most one remote acts per line per call)."""
        return _jitted_run_ops_mn(self.subset.name, self.shared_credits,
                                  self.n_homes, self.home_bw,
                                  self.kernel_backend)(
            st, opv, op_val, self.delays, self.credits,
            jnp.asarray(max_rounds, jnp.int32))
