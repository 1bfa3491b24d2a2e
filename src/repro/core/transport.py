"""Virtual-channel transport layer (paper §4.2).

The reference ECI implementation multiplexes 14 virtual channels: 10 carry
coherence traffic (split into request/response classes, with separate VC sets
for odd and even cache lines for load balancing), the rest carry IO/barrier
traffic.  The transport guarantees *reliable delivery* and *no ordering
across VCs*; deadlock freedom comes from separating message classes onto
distinct VCs plus credit-based flow control.

Here the same semantics are modelled over JAX arrays:

* each line has at most one outstanding transaction per direction (an MSHR
  per line, as in real directories);
* a message in flight is (msg, dirty, payload, age); it is DELIVERED when its
  age reaches the per-VC delay — distinct per-VC delays reorder delivery
  *across* VCs exactly as the real link does;
* per-VC credit counters bound the number of in-flight messages; submissions
  without credit stall (and are retried by the caller), never dropped.

``vc_of(line, msg_class)`` reproduces the odd/even interleaving.

Every operation is polymorphic over LEADING batch axes: a channel whose
fields are ``[L]`` models one initiator (the 2-node engine), ``[R, L]``
models R initiators over one contiguous flat slab (the N-remote engine) —
same code path, no ``vmap`` wrapper, so the traced program carries a
single batched op per phase regardless of R.  Credits are accounted PER
INITIATOR (each leading-axis row ranks its own candidates against the
per-VC limit), which is exactly the semantics the old per-remote ``vmap``
gave and what the N-remote bisimulation tests pin down.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .messages import MsgType

# Message classes, each mapped to its own VC pair (odd/even lines).
CLASS_REMOTE_REQ = 0    # remote -> home coherence requests
CLASS_HOME_RESP = 1     # home -> remote responses
CLASS_HOME_REQ = 2      # home -> remote (home-initiated downgrades)
CLASS_REMOTE_RESP = 3   # remote -> home responses to home requests
CLASS_IO = 4            # non-coherent IO/barrier/IPI traffic
N_CLASSES = 5

#: 10 coherence VCs (5 classes x odd/even) as in the reference design; the
#: remaining 4 of the paper's 14 carry traffic we do not model separately.
N_VCS = 2 * N_CLASSES

#: Per-VC delivery delay in engine steps.  Distinct values across VCs model
#: cross-VC reordering (there are NO ordering guarantees across VCs).
DEFAULT_DELAYS = np.asarray([1, 2, 1, 3, 2, 1, 3, 1, 2, 2], np.int32)

#: Per-VC credits (max messages in flight).
DEFAULT_CREDITS = np.asarray([64] * N_VCS, np.int32)


def vc_of(line, msg_class):
    """VC id for a (line, class): odd/even interleaving within the class."""
    return msg_class * 2 + (line & 1)


def vc_value(values: jnp.ndarray, line, msg_class: int) -> jnp.ndarray:
    """``values[vc_of(line, msg_class)]`` as a parity select — a per-line
    gather is an indexed load per element on a TPU."""
    return jnp.where((line & 1) == 1, values[2 * msg_class + 1],
                     values[2 * msg_class])


class Channel(NamedTuple):
    """One direction of per-line in-flight messages (struct-of-arrays).

    Fields may carry any leading batch shape: ``[L]``/``[L, B]`` for one
    initiator, ``[R, L]``/``[R, L, B]`` for the N-remote flat layout."""

    msg: jnp.ndarray       # [..., L] int8, MsgType (NOP = empty slot)
    dirty: jnp.ndarray     # [..., L] bool
    payload: jnp.ndarray   # [..., L, B] line data
    age: jnp.ndarray       # [..., L] int32


def make_channel(n_lines: int, block: int, dtype=jnp.float32) -> Channel:
    return Channel(
        msg=jnp.zeros((n_lines,), jnp.int8),
        dirty=jnp.zeros((n_lines,), bool),
        payload=jnp.zeros((n_lines, block), dtype),
        age=jnp.zeros((n_lines,), jnp.int32),
    )


def occupancy(ch: Channel, msg_class: int) -> jnp.ndarray:
    """Per-VC occupancy ``[..., N_VCS]`` of a channel carrying
    ``msg_class`` — one row per leading-axis initiator."""
    vcs = vc_of(jnp.arange(ch.msg.shape[-1]), msg_class)
    onehot = jax.nn.one_hot(vcs, N_VCS, dtype=jnp.int32)       # [L, V]
    active = (ch.msg != int(MsgType.NOP)).astype(jnp.int32)
    return jnp.einsum("...l,lv->...v", active, onehot)


def credit_accept(ch: Channel, msg_class: int, cand: jnp.ndarray,
                  credits: jnp.ndarray, *,
                  shared: bool = False,
                  backend: str = "xla") -> jnp.ndarray:
    """[..., L] mask of candidates within their VC's credit.

    A candidate is in credit iff its VC's current occupancy plus the number
    of earlier candidates on the same VC stays below the credit (stable
    line order within each leading-axis initiator row).  A message class
    only ever touches its own odd/even VC pair, so the ranking reduces to
    two parity-split running sums over the line axis — bit-identical to
    (and much cheaper than) ranking against a dense ``[..., L, N_VCS]``
    one-hot expansion.

    ``shared=True`` models a SHARED-credit link instead of per-initiator
    credit pools: occupancy and candidate ranks reduce over the LAST TWO
    axes — the ``[initiators, lines]`` slab (row-major order ranks
    candidates across rows), so one credit budget covers the whole
    ``[R, L]`` plane.  Any further LEADING axes keep independent pools:
    the multi-home engine's ``[H, R, L/H]`` layout gives each home slice
    its own shared budget, since credit pools — like everything else in
    the home plane — live at the directory slice.  This is the ROADMAP's
    shared-credit question for the home's R-1 invalidation fan-out — the
    per-row accounting gives the home R independent budgets, a real
    shared link would not.

    ``backend="pallas"`` routes the per-row ranking through the
    ``kernels.coherency_step.credit_rank`` Pallas kernel — BIT-identical
    to the default XLA expressions (integer arithmetic); the shared-pool
    path always uses the jnp expressions.
    """
    with jax.named_scope("eci.credit_rank"):
        L = ch.msg.shape[-1]
        odd = (jnp.arange(L) & 1).astype(bool)                  # [L]
        active = ch.msg != int(MsgType.NOP)
        if shared and ch.msg.ndim > 1:
            c_o = jnp.where(odd, cand, False).astype(jnp.int32)
            c_e = jnp.where(odd, False, cand).astype(jnp.int32)
            occ_o = jnp.where(odd, active, False).sum(
                axis=(-2, -1), keepdims=True)
            occ_e = jnp.where(odd, False, active).sum(
                axis=(-2, -1), keepdims=True)
            flat_o = c_o.reshape(c_o.shape[:-2] + (-1,))
            flat_e = c_e.reshape(c_e.shape[:-2] + (-1,))
            rank_o = (jnp.cumsum(flat_o, axis=-1)
                      - flat_o).reshape(cand.shape)
            rank_e = (jnp.cumsum(flat_e, axis=-1)
                      - flat_e).reshape(cand.shape)
            occ_rank = jnp.where(odd, occ_o + rank_o, occ_e + rank_e)
        elif backend == "pallas":
            from ..kernels import ops as _kops
            occ_rank = _kops.credit_rank(active, cand)
        else:
            c_o = jnp.where(odd, cand, False).astype(jnp.int32)
            c_e = jnp.where(odd, False, cand).astype(jnp.int32)
            occ_o = jnp.where(odd, active, False).sum(-1, keepdims=True)
            occ_e = jnp.where(odd, False, active).sum(-1, keepdims=True)
            rank_o = jnp.cumsum(c_o, axis=-1) - c_o    # candidates before me
            rank_e = jnp.cumsum(c_e, axis=-1) - c_e
            occ_rank = jnp.where(odd, occ_o + rank_o, occ_e + rank_e)
        vc_credit = vc_value(credits, jnp.arange(L), msg_class)  # [L]
        return cand & (occ_rank < vc_credit)


def place(ch: Channel, accept: jnp.ndarray, msg: jnp.ndarray,
          dirty: jnp.ndarray, payload: jnp.ndarray) -> Channel:
    """Write messages into slots for an acceptance mask ALREADY decided.

    The single-ranking fast path: a caller that dry-ran ``credit_accept``
    earlier in the step (and whose final emission set can only have SHRUNK
    since — fewer candidates means smaller ranks on unchanged occupancy)
    reuses that verdict instead of ranking a second time."""
    with jax.named_scope("eci.transport"):
        return Channel(
            msg=jnp.where(accept, msg.astype(jnp.int8), ch.msg),
            dirty=jnp.where(accept, dirty, ch.dirty),
            payload=jnp.where(accept[..., None], payload, ch.payload),
            age=jnp.where(accept, 0, ch.age),
        )


def submit(ch: Channel, msg_class: int, want: jnp.ndarray, msg: jnp.ndarray,
           dirty: jnp.ndarray, payload: jnp.ndarray,
           credits: jnp.ndarray, *,
           unbounded: bool = False,
           shared: bool = False,
           backend: str = "xla") -> tuple[Channel, jnp.ndarray]:
    """Try to enqueue messages for lines where ``want`` is set.

    Returns the updated channel and the mask of ACCEPTED lines.  A submit is
    refused when the slot is busy or the target VC is out of credit (credit
    exhaustion is resolved conservatively: if the VC's occupancy plus the
    number of earlier accepted lines on that VC reaches the credit, later
    lines stall until a future step).  Credit ranking is per leading-axis
    initiator (stable line order within each row).

    ``unbounded=True`` skips the credit ranking entirely — the response-
    class fast path (responses always sink: the deadlock-freedom argument),
    identical to passing effectively-infinite credits but without paying
    the occupancy/rank computation every step.  ``shared=True`` accounts
    credits across all leading axes (see ``credit_accept``).
    """
    with jax.named_scope("eci.transport"):
        free = ch.msg == int(MsgType.NOP)
        cand = want & free                                      # [..., L]
        accept = cand if unbounded else credit_accept(
            ch, msg_class, cand, credits, shared=shared, backend=backend)
        return place(ch, accept, msg, dirty, payload), accept


def tick(ch: Channel) -> Channel:
    """Advance time for all in-flight messages."""
    with jax.named_scope("eci.transport"):
        active = ch.msg != int(MsgType.NOP)
        return ch._replace(age=jnp.where(active, ch.age + 1, ch.age))


def any_in_flight(ch: Channel) -> jnp.ndarray:
    """[..., L] bool — any message in flight per line across the channel's
    remote axis (the per-line completion/lock reduction the engines run
    each step; shared by the dense and packed directory layouts)."""
    return (ch.msg != int(MsgType.NOP)).any(axis=-2)


def deliver(ch: Channel, msg_class: int, delays: jnp.ndarray,
            delay_l: jnp.ndarray = None) -> tuple[Channel, jnp.ndarray]:
    """Pop messages whose age has reached their VC's delay.

    Returns (channel with delivered slots freed, delivered mask).  The
    message fields for delivered lines should be read from ``ch`` (the input)
    under the returned mask.  ``delay_l`` optionally supplies the per-line
    delay vector ``delays[vc_of(lines, msg_class)]`` precomputed once by the
    caller — the engines hoist one gather per VC pair out of the per-site
    bodies of their fused steps.
    """
    with jax.named_scope("eci.transport"):
        if delay_l is None:
            delay_l = vc_value(delays, jnp.arange(ch.msg.shape[-1]),
                               msg_class)
        ready = (ch.msg != int(MsgType.NOP)) & (ch.age >= delay_l)
        freed = ch._replace(msg=jnp.where(ready, int(MsgType.NOP),
                                          ch.msg).astype(jnp.int8))
        return freed, ready
