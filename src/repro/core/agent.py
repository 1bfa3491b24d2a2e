"""Remote caching agent: the 4-state protocol of Fig. 1(b), vectorized.

The remote node (the consumer — on Enzian the CPU; here a data-parallel
replica reading through the coherent tier) only ever sees the merged joint
states ``*S, *I, IE, IM`` (requirements 6/7 make this sound), so the agent is
a 4-state machine per line plus one MSHR (pending transaction) per line.

Intermediate states are represented explicitly: ``pending_req != NOP`` marks
a line with a request in flight (the paper's "additional intermediate states,
invisible to the application").

Every function is polymorphic over LEADING batch axes: ``[L]`` fields model
one agent (the 2-node engine), ``[R, L]`` model the N-remote engine's R
agents over one contiguous slab — the scalar counters (``illegal``,
``hits``, ``misses``) reduce over the LINE axis only, so they stay scalars
for one agent and ``[R]`` per-remote tallies for the batched layout.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax.numpy as jnp

from .messages import MsgType
from .protocol import DenseTables, LocalOp, lookup
from .states import RemoteState


class AgentState(NamedTuple):
    remote_state: jnp.ndarray   # [L] int8 RemoteState
    cache: jnp.ndarray          # [L, B] local copy (valid when != I)
    pending_req: jnp.ndarray    # [L] int8 MsgType in flight (NOP = none)
    pending_op: jnp.ndarray     # [L] int8 LocalOp to complete after grant
    pending_val: jnp.ndarray    # [L, B] store value awaiting grant
    illegal: jnp.ndarray        # [] int32
    hits: jnp.ndarray           # [] int32  (paper Fig. 8: locality reuse)
    misses: jnp.ndarray         # [] int32


def plane_shape(agents: AgentState) -> tuple:
    """(R, L) of a batched-agent state: the canonical dense plane shape.

    The engines derive R/L from here rather than from directory/MSHR
    slabs, whose layout changes under the bit-packed planes
    (``EngineConfig.packed``) while the agent plane stays dense.
    """
    return agents.remote_state.shape[-2:]


def make_agent(n_lines: int, block: int, dtype=jnp.float32) -> AgentState:
    return AgentState(
        remote_state=jnp.zeros((n_lines,), jnp.int8),
        cache=jnp.zeros((n_lines, block), dtype),
        pending_req=jnp.zeros((n_lines,), jnp.int8),
        pending_op=jnp.zeros((n_lines,), jnp.int8),
        pending_val=jnp.zeros((n_lines, block), dtype),
        illegal=jnp.zeros((), jnp.int32),
        hits=jnp.zeros((), jnp.int32),
        misses=jnp.zeros((), jnp.int32),
    )


def submit(tables: DenseTables, st: AgentState, op: jnp.ndarray,
           value: jnp.ndarray
           ) -> Tuple[AgentState, jnp.ndarray, jnp.ndarray, jnp.ndarray,
                      jnp.ndarray]:
    """Issue local ops (LOAD/STORE/EVICT/DEMOTE) against the agent.

    Ops on lines with a pending transaction are REFUSED (returned in the
    ``accepted`` mask) — one MSHR per line.  Hits complete immediately
    (silent transitions applied); misses emit a request.

    MULTI-OP ISSUE: the op vector is dense over lines, so one agent (one
    leading-axis row) may issue SEVERAL new ops in a single call — one per
    distinct line, each allocating its own line MSHR.  This is the agent
    half of the streaming driver's issue width W (``traffic.driver``): the
    driver guarantees at most one op per (agent, line) per step by
    serializing same-line window slots in-queue, and this function
    guarantees per-line MSHR exclusivity; nothing here assumes a single op
    per agent per step.  The hit/miss counters reduce over the line axis,
    so they stay exact under multi-op issue.

    Returns (state, accepted[L], request_msg[L], req_dirty[L], req_payload).
    """
    o = op.astype(jnp.int32)
    rs = st.remote_state.astype(jnp.int32)
    idle = st.pending_req == int(MsgType.NOP)
    wants = o != int(LocalOp.NOP)
    accepted = wants & idle

    new_state = lookup(tables.loc_new_state, o, rs)
    request = lookup(tables.loc_request, o, rs)
    req_dirty = lookup(tables.loc_req_dirty, o, rs)
    hit = lookup(tables.loc_hit, o, rs)

    is_hit = accepted & hit
    is_miss = accepted & ~hit
    is_store_hit = is_hit & (o == int(LocalOp.STORE))

    # hits: apply silent transition + store data now.
    remote_state = jnp.where(is_hit, new_state.astype(jnp.int8),
                             st.remote_state)
    cache = jnp.where(is_store_hit[..., None], value, st.cache)
    # evictions/demotions may carry the dirty line as request payload; after
    # a voluntary downgrade the line content for S stays, for I is dead.
    req_payload = st.cache

    # misses: park the op, emit the request.
    pending_req = jnp.where(is_miss, request.astype(jnp.int8),
                            st.pending_req)
    pending_op = jnp.where(is_miss, op.astype(jnp.int8), st.pending_op)
    pending_val = jnp.where(is_miss[..., None], value, st.pending_val)

    emit = jnp.where(accepted & (request != int(MsgType.NOP)),
                     request.astype(jnp.int8),
                     jnp.int8(int(MsgType.NOP)))

    # hit/miss accounting over loads (temporal-locality experiments).
    is_load = accepted & (o == int(LocalOp.LOAD))
    new = AgentState(
        remote_state=remote_state,
        cache=cache,
        pending_req=pending_req,
        pending_op=pending_op,
        pending_val=pending_val,
        illegal=st.illegal,
        hits=st.hits + (is_load & hit).sum(axis=-1).astype(jnp.int32),
        misses=st.misses + (is_load & ~hit).sum(axis=-1).astype(jnp.int32),
    )
    return new, accepted, emit, req_dirty, req_payload


def on_response(tables: DenseTables, st: AgentState, active: jnp.ndarray,
                resp: jnp.ndarray, payload: jnp.ndarray,
                nack_holds: bool = False) -> Tuple[AgentState, jnp.ndarray]:
    """Complete pending transactions with their responses.

    Returns (state, retry[L]) — retry marks NACKed lines whose op should be
    resubmitted by the caller.

    ``nack_holds=True`` (the N-remote engine) keeps the CURRENT state on a
    NACK instead of the table's fallback: with several remotes a home-
    initiated invalidation can cross the request in flight, so the agent
    may already have been downgraded below the state it requested from —
    the retry then reissues from wherever it actually is.
    """
    req = st.pending_req.astype(jnp.int32)
    rm = resp.astype(jnp.int32)
    new_state = lookup(tables.resp_new_state, req, rm).astype(jnp.int32)
    legal = new_state >= 0
    do = active & legal
    nack = active & (rm == int(MsgType.RESP_NACK))
    if nack_holds:
        new_state = jnp.where(nack, st.remote_state.astype(jnp.int32),
                              new_state)

    carries = (rm == int(MsgType.RESP_DATA)) | (rm == int(MsgType.RESP_DATA_DIRTY))
    cache = jnp.where((do & carries)[..., None], payload, st.cache)

    # complete the parked op: a parked STORE writes now and dirties the line.
    is_store = do & (st.pending_op == int(LocalOp.STORE)) & ~nack
    cache = jnp.where(is_store[..., None], st.pending_val, cache)
    state_after = jnp.where(is_store, int(RemoteState.M), new_state)

    remote_state = jnp.where(do, state_after.astype(jnp.int8),
                             st.remote_state)
    new = st._replace(
        remote_state=remote_state,
        cache=cache,
        pending_req=jnp.where(do, jnp.int8(int(MsgType.NOP)),
                              st.pending_req),
        pending_op=jnp.where(do & ~nack, jnp.int8(int(LocalOp.NOP)),
                             st.pending_op),
        illegal=st.illegal + (active & ~legal).sum(axis=-1).astype(jnp.int32),
    )
    return new, nack


def on_home_msg(tables: DenseTables, st: AgentState, active: jnp.ndarray,
                msg: jnp.ndarray
                ) -> Tuple[AgentState, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Process home-initiated downgrades (transitions 8, 9).

    Returns (state, resp_msg, resp_dirty, resp_payload) — the reply is
    mandatory (requirement 2 / Table 1).
    """
    m = msg.astype(jnp.int32)
    rs = st.remote_state.astype(jnp.int32)
    new_state = lookup(tables.rem_new_state, m, rs)
    resp = lookup(tables.rem_resp, m, rs)
    resp_dirty = lookup(tables.rem_resp_dirty, m, rs)
    legal = lookup(tables.rem_legal, m, rs)
    do = active & legal
    new = st._replace(
        remote_state=jnp.where(do, new_state.astype(jnp.int8),
                               st.remote_state),
        illegal=st.illegal + (active & ~legal).sum(axis=-1).astype(jnp.int32),
    )
    resp = jnp.where(do, resp.astype(jnp.int8), jnp.int8(int(MsgType.NOP)))
    return new, resp, jnp.where(do, resp_dirty, False), st.cache


def read_hit_values(st: AgentState, lines_mask: jnp.ndarray) -> jnp.ndarray:
    """[L, B] cache content for lines held in a readable state."""
    readable = st.remote_state != int(RemoteState.I)
    return jnp.where((lines_mask & readable)[..., None], st.cache, 0)
