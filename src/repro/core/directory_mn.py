"""Sharer-vector home directory for the N-remote engine (paper §4.1).

The 2-node directory (``core.directory``) tracks ONE remote view per line;
this one keeps a full view VECTOR ``[R, L]`` — the classic full-map
directory (Censier-Feautrier, the paper's ref [10]) with the sharer bitmask
being ``view != I``.  Three vectorized operations cover the protocol:

* ``absorb`` — downgrade payloads arriving at the home (voluntary evictions
  and replies to home-initiated downgrades), applied per-remote with the
  at-most-one-dirty-source-per-line reduction;
* ``grant`` — complete a request once its fan-out preconditions hold
  (no other owner for a shared grant; every other view I for an exclusive
  one), keyed on (msg, home state) via the baked ``DenseTablesMN``;
* ``needed_downgrades`` — the write-invalidate fan-out rule: one
  ``HOME_DOWNGRADE_*`` per conflicting sharer, the message-count cost of
  scaling that motivates the paper's 2-node subsetting (§3.4).

All of it is gathers and masked updates over dense arrays — fully
``jit``-able, no python control flow in the hot path.

Like the transport and agent primitives, every function here is
polymorphic over LEADING batch axes: the canonical layout is ``[R, L]``
views over ``[L]`` home state (one directory), and the multi-home engine
runs the same code over ``[H, R, L/H]`` views / ``[H, L/H]`` home state —
one batched program per phase, H home slices, no ``vmap``.  The remote
axis is therefore always ``axis=-2`` of ``view`` and per-remote gathers
use ``take_along_axis`` along it.

BIT-PACKED PLANES (opt-in, ``EngineConfig.packed``): the hardware
directory the paper shards keeps the sharer set as a compact bitmap per
line (§3; BedRock's dense directory makes the same choice), and this
module can run the same layout — ``view`` becomes two ``[L, W]`` uint32
word planes (``W = ceil(R/32)``): plane ``PLANE_PRES`` has bit ``r`` set
where remote ``r``'s view is non-I, plane ``PLANE_EXCL`` where it is EM
(``EXCL ⊆ PRES``; the view code is reconstructed as EM/S/I from the two
bits).  The sharer reductions (``no_sharers``, fan-out target sets)
become AND/OR/any word ops over 2·W words per line instead of R int8
rows — a 4–32x cut in per-step directory traffic at R=64.  Every
function below branches on ``view.dtype`` (a trace-time constant:
``jax.jit`` keys on avals, so dense and packed states compile separate
programs and the DENSE program is the exact pre-packing one).  Pad bits
past R stay zero by construction: ``pack_mask`` pads with zeros, word
updates are AND/OR against masks whose pad bits are zero, and
``write_bit`` only ever touches a real requester's bit.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax.numpy as jnp
import numpy as np

from .messages import MsgType
from .protocol import MN_REQUEST_VIEW, DenseTablesMN, MnAbsorb, lookup
from .states import HomeState, RemoteView

#: Plane indices of the packed ``[2, L, W]`` view array.
PLANE_PRES = 0   # bit r set <=> remote r's view != I (the sharer bitmap)
PLANE_EXCL = 1   # bit r set <=> remote r's view == EM (subset of PRES)


def n_words(n_remotes: int) -> int:
    """Words per line of a packed plane: ``ceil(R / 32)``."""
    return (n_remotes + 31) // 32


def pack_mask(mask: jnp.ndarray) -> jnp.ndarray:
    """``[..., R, L]`` bool -> ``[..., L, W]`` uint32 bitmask words.

    Bit ``r % 32`` of word ``r // 32`` carries remote ``r``; pad bits
    past R are zero."""
    R, L = mask.shape[-2:]
    W = n_words(R)
    m = jnp.moveaxis(mask, -2, -1)                       # [..., L, R]
    if W * 32 != R:
        m = jnp.concatenate(
            [m, jnp.zeros(m.shape[:-1] + (W * 32 - R,), bool)], axis=-1)
    m = m.reshape(m.shape[:-1] + (W, 32))
    bits = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    return jnp.where(m, bits, jnp.uint32(0)).sum(axis=-1,
                                                 dtype=jnp.uint32)


def unpack_mask(words: jnp.ndarray, n_remotes: int) -> jnp.ndarray:
    """``[..., L, W]`` uint32 -> ``[..., R, L]`` bool (inverse of
    ``pack_mask``; pad bits are dropped)."""
    W = words.shape[-1]
    shifts = jnp.arange(32, dtype=jnp.uint32)
    b = (words[..., None] >> shifts) & jnp.uint32(1)     # [..., L, W, 32]
    b = b.reshape(b.shape[:-2] + (W * 32,))
    return jnp.moveaxis(b, -1, -2)[..., :n_remotes, :] != 0


def node_hot(node: jnp.ndarray, W: int) -> jnp.ndarray:
    """``[..., L, W]`` one-hot word mask of per-line remote id ``node``."""
    sel = jnp.arange(W) == (node // 32)[..., None]
    return jnp.where(
        sel, jnp.uint32(1) << (node % 32).astype(jnp.uint32)[..., None],
        jnp.uint32(0))


def get_bit(words: jnp.ndarray, node: jnp.ndarray) -> jnp.ndarray:
    """``[..., L]`` bool — per-line bit of remote ``node`` (``[..., L]``
    int) in a ``[..., L, W]`` word plane."""
    w = jnp.take_along_axis(words, (node // 32)[..., None],
                            axis=-1)[..., 0]
    return ((w >> (node % 32).astype(jnp.uint32)) & jnp.uint32(1)) != 0


def write_bit(words: jnp.ndarray, do_set: jnp.ndarray,
              do_clear: jnp.ndarray, node: jnp.ndarray) -> jnp.ndarray:
    """Set/clear per-line requester bits in a word plane (masked lines
    only; ``do_set``/``do_clear`` are ``[..., L]`` and disjoint)."""
    hot = node_hot(node, words.shape[-1])
    words = jnp.where(do_set[..., None], words | hot, words)
    return jnp.where(do_clear[..., None], words & ~hot, words)


def any_bits(words: jnp.ndarray, backend: str = "xla") -> jnp.ndarray:
    """``[..., L]`` bool — any bit set in the line's words (the packed
    sharer-present reduction; popcount-style Pallas kernel under the
    "pallas" backend, bit-identical)."""
    if backend == "pallas":
        from ..kernels import ops as _kops
        return _kops.packed_any(words)
    return (words != 0).any(axis=-1)


class DirectoryMNState(NamedTuple):
    home_state: jnp.ndarray   # [L] int8 HomeState
    view: jnp.ndarray         # [R, L] int8 RemoteView per remote — or the
    #                           packed [2, L, W] uint32 PRES/EXCL planes
    backing: jnp.ndarray      # [L, B] at-rest data
    home_buf: jnp.ndarray     # [L, B] home's copy (valid when state != I)
    illegal: jnp.ndarray      # [] int32


def make_directory_mn(backing: jnp.ndarray, n_remotes: int,
                      packed: bool = False) -> DirectoryMNState:
    n_lines = backing.shape[0]
    view = (jnp.zeros((2, n_lines, n_words(n_remotes)), jnp.uint32)
            if packed else jnp.zeros((n_remotes, n_lines), jnp.int8))
    return DirectoryMNState(
        home_state=jnp.zeros((n_lines,), jnp.int8),
        view=view,
        backing=backing,
        home_buf=jnp.zeros_like(backing),
        illegal=jnp.zeros((), jnp.int32),
    )


def view_of(st: DirectoryMNState, node: jnp.ndarray) -> jnp.ndarray:
    """``[..., L]`` int32 — the per-line requester's ``RemoteView`` code,
    layout-agnostic (the dense path is verbatim the engine's historical
    ``_take_remote(view, node)`` gather)."""
    if st.view.dtype == jnp.uint32:
        pres = get_bit(st.view[..., PLANE_PRES, :, :], node)
        excl = get_bit(st.view[..., PLANE_EXCL, :, :], node)
        return jnp.where(
            excl, int(RemoteView.EM),
            jnp.where(pres, int(RemoteView.S),
                      int(RemoteView.I))).astype(jnp.int32)
    return _take_remote(st.view, node).astype(jnp.int32)


def _take_remote(arr: jnp.ndarray, node: jnp.ndarray) -> jnp.ndarray:
    """``arr[..., node[l], l]`` — one remote's row per line.

    ``arr`` is ``[..., R, L]`` (or ``[..., R, L, B]``), ``node`` is
    ``[..., L]`` with every id in ``[0, R)``.  A select chain over the
    remote axis, not a gather: it is one fused pass over the plane on a
    TPU, where a per-line gather is an indexed load per element.  Same
    op for the flat and the home-batched layouts."""
    payload = arr.ndim == node.ndim + 2
    sel = node[..., None] if payload else node
    rows = jnp.moveaxis(arr, -3 if payload else -2, 0)
    out = rows[0]
    for r in range(1, rows.shape[0]):
        out = jnp.where(sel == r, rows[r], out)
    return out


def home_value(st: DirectoryMNState) -> jnp.ndarray:
    """[..., L, B] — the line value as seen by the home (own copy if
    cached)."""
    has = st.home_state != int(HomeState.I)
    return jnp.where(has[..., None], st.home_buf, st.backing)


def absorb(tables: DenseTablesMN, st: DirectoryMNState,
           active: jnp.ndarray, kind: jnp.ndarray, dirty: jnp.ndarray,
           payload: jnp.ndarray, backend: str = "xla"
           ) -> DirectoryMNState:
    """Apply per-remote downgrade-ish arrivals to the directory.

    Args:
      active: [R, L] bool — remote r delivered an absorbable message on l.
      kind: [R, L] int8 MnAbsorb kind.
      dirty: [R, L] bool — the message carried a dirty payload.
      payload: [R, L, B] — line data (valid where dirty).

    View updates commute across remotes; at most one absorb per line can be
    dirty (single-writer invariant), so home-state/data effects reduce over
    R by selecting the unique dirty source.

    A STATELESS home (``tables.stateless_home``) tracks no per-line state:
    voluntary downgrades are absorbed by doing nothing at all (the subset's
    workload guarantee — no STOREs — means the payload can never be dirty,
    so there is nothing to write back either).
    """
    if tables.stateless_home:
        return st
    vol_i = int(MnAbsorb.VOL_I)
    rep_s = int(MnAbsorb.REPLY_S)
    rep_i = int(MnAbsorb.REPLY_I)

    packed = st.view.dtype == jnp.uint32

    # -- per-remote view updates ------------------------------------------
    to_i = active & ((kind == vol_i) | (kind == rep_i))
    if packed:
        # to_i/to_s are disjoint (kind is single-valued per lane), so the
        # dense pair of masked stores is one AND-NOT + OR per word plane.
        # A clean REPLY_S only confirms S where the home still believes EM
        # (the EXCL bit) — see the dense branch's race note below.
        pres = st.view[..., PLANE_PRES, :, :]
        excl = st.view[..., PLANE_EXCL, :, :]
        rep_s_act = active & (kind == rep_s)
        to_i_w = pack_mask(to_i)
        to_s_w = (pack_mask(rep_s_act) & excl) | pack_mask(rep_s_act & dirty)
        pres2 = (pres & ~to_i_w) | to_s_w
        excl2 = excl & ~to_i_w & ~to_s_w
        view = jnp.stack([pres2, excl2], axis=-3)
    else:
        # a clean reply to a recall-to-shared only confirms S if the home
        # still believes EM — a crossing voluntary eviction may already have
        # cleared the view, and the remote is then truly I (races, §3.3).
        to_s = active & (kind == rep_s) & \
            ((st.view == int(RemoteView.EM)) | dirty)
        view = jnp.where(to_i, jnp.int8(int(RemoteView.I)), st.view)
        view = jnp.where(to_s, jnp.int8(int(RemoteView.S)), view)

    # -- home-state / data effects (at most one dirty source per line) -----
    d_act = active & dirty                           # [..., R, L]
    any_dirty = d_act.any(axis=-2)                   # [..., L]
    src = jnp.argmax(d_act, axis=-2)                 # [..., L] dirty remote
    d_kind = _take_remote(kind, src).astype(jnp.int32)     # [..., L]
    d_pay = _take_remote(payload, src)               # [..., L, B]

    hs = st.home_state.astype(jnp.int32)
    one = jnp.ones_like(hs)
    new_home = lookup(tables.absorb_new_home, d_kind, one, hs)
    to_back = lookup(tables.absorb_to_backing, d_kind, one, hs) & any_dirty
    to_buf = lookup(tables.absorb_to_homebuf, d_kind, one, hs) & any_dirty

    home_state = jnp.where(any_dirty, new_home.astype(jnp.int8),
                           st.home_state)
    backing = jnp.where(to_back[..., None], d_pay, st.backing)
    home_buf = jnp.where(to_buf[..., None], d_pay, st.home_buf)

    # hidden-O upkeep: when the LAST sharer leaves a hidden-O line, the home
    # is simply dirty-exclusive again (O -> M); the invariant "hidden O only
    # while sharers exist" stays true at quiescence.
    if packed:
        no_sharers = ~any_bits(pres2, backend)
    else:
        no_sharers = ~(view != int(RemoteView.I)).any(axis=-2)
    was_vol = (active & (kind == vol_i)).any(axis=-2)
    o_to_m = was_vol & no_sharers & \
        (home_state == int(HomeState.O))
    home_state = jnp.where(o_to_m, jnp.int8(int(HomeState.M)), home_state)

    return st._replace(home_state=home_state, view=view,
                       backing=backing, home_buf=home_buf)


def needed_downgrades(st: DirectoryMNState, active: jnp.ndarray,
                      msg: jnp.ndarray, node: jnp.ndarray) -> jnp.ndarray:
    """[..., R, L] int8 — the HOME_DOWNGRADE_* each remote needs before
    ``msg`` from ``node`` can be granted (NOP where none).  The vectorized
    twin of ``protocol.mn_needed_mask``."""
    R = st.view.shape[-2]
    rids = jnp.arange(R)[:, None]                    # [R, 1]
    others = rids != node[..., None, :]              # [..., R, L]
    shared_req = active & (msg == int(MsgType.REQ_READ_SHARED))
    excl_req = active & ((msg == int(MsgType.REQ_READ_EXCL))
                         | (msg == int(MsgType.REQ_UPGRADE)))
    recall = shared_req[..., None, :] & others & \
        (st.view == int(RemoteView.EM))
    inval = excl_req[..., None, :] & others & \
        (st.view != int(RemoteView.I))
    out = jnp.where(inval, jnp.int8(int(MsgType.HOME_DOWNGRADE_I)),
                    jnp.int8(int(MsgType.NOP)))
    return jnp.where(recall, jnp.int8(int(MsgType.HOME_DOWNGRADE_S)), out)


def home_needed_downgrades(st: DirectoryMNState, want_read: jnp.ndarray,
                           want_write: jnp.ndarray) -> jnp.ndarray:
    """[..., R, L] int8 — downgrades required before a HOME-side access:
    reads recall a dirty owner to S, writes invalidate every sharer."""
    recall = want_read[..., None, :] & (st.view == int(RemoteView.EM))
    inval = want_write[..., None, :] & (st.view != int(RemoteView.I))
    out = jnp.where(inval, jnp.int8(int(MsgType.HOME_DOWNGRADE_I)),
                    jnp.int8(int(MsgType.NOP)))
    return jnp.where(recall & ~inval,
                     jnp.int8(int(MsgType.HOME_DOWNGRADE_S)), out)


def needed_words(st: DirectoryMNState, active: jnp.ndarray,
                 msg: jnp.ndarray, node: jnp.ndarray,
                 backend: str = "xla"
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Packed twin of ``needed_downgrades``: ``(recall_w, inval_w)``
    ``[..., L, W]`` word planes of the remotes that need HOME_DOWNGRADE_S
    (recall) / HOME_DOWNGRADE_I (invalidate) before ``msg`` from ``node``
    can be granted.  The ``others & (view == ...)`` row compares collapse
    to one AND-NOT-hot per plane; ``shared_req``/``excl_req`` are
    per-line disjoint (``msg`` is single-valued), so the planes never
    overlap on a line — bit r set in either plane corresponds exactly to
    a non-NOP lane of the dense output."""
    shared_req = active & (msg == int(MsgType.REQ_READ_SHARED))
    excl_req = active & ((msg == int(MsgType.REQ_READ_EXCL))
                         | (msg == int(MsgType.REQ_UPGRADE)))
    pres = st.view[..., PLANE_PRES, :, :]
    excl = st.view[..., PLANE_EXCL, :, :]
    if backend == "pallas":
        from ..kernels import ops as _kops
        return _kops.packed_fanout(pres, excl, node, shared_req, excl_req)
    hot = node_hot(node, pres.shape[-1])
    recall_w = jnp.where(shared_req[..., None], excl & ~hot,
                         jnp.uint32(0))
    inval_w = jnp.where(excl_req[..., None], pres & ~hot, jnp.uint32(0))
    return recall_w, inval_w


def home_needed_words(st: DirectoryMNState, want_read: jnp.ndarray,
                      want_write: jnp.ndarray
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Packed twin of ``home_needed_downgrades``.  The dense twin gives
    HOME_DOWNGRADE_I precedence where a lane wants both (read + write),
    so the recall plane masks out invalidated bits."""
    pres = st.view[..., PLANE_PRES, :, :]
    excl = st.view[..., PLANE_EXCL, :, :]
    inval_w = jnp.where(want_write[..., None], pres, jnp.uint32(0))
    recall_w = jnp.where(want_read[..., None], excl,
                         jnp.uint32(0)) & ~inval_w
    return recall_w, inval_w


def grant(tables: DenseTablesMN, st: DirectoryMNState, active: jnp.ndarray,
          msg: jnp.ndarray, node: jnp.ndarray
          ) -> Tuple[DirectoryMNState, jnp.ndarray, jnp.ndarray]:
    """Complete requests whose downgrade preconditions hold.

    Args:
      active: [..., L] bool — a grant fires on the line this step.
      msg: [..., L] int8 — the parked request type.
      node: [..., L] int32 — the requester.

    Returns (new_state, resp [..., L] int8 (NOP where inactive),
    payload [..., L, B]).
    An UPGRADE whose requester view was concurrently invalidated is NACKed
    (the agent falls back to I and reissues READ_EXCL) — the transaction-
    layer race of §3.3, kept rare by per-line serialization.

    A STATELESS home answers READ_SHARED from the at-rest data and records
    NOTHING: no view write, no home-state transition (the single joint
    state ``I*`` of §3.4).  Requests outside the subset still count as
    illegal (the baked ``grant_legal`` mask).
    """
    m = msg.astype(jnp.int32)
    hs = st.home_state.astype(jnp.int32)
    req_view = view_of(st, node)                          # requester's

    want_view = lookup(np.asarray(
        [MN_REQUEST_VIEW.get(i, 0) for i in range(16)], np.int32), m)
    legal = lookup(tables.grant_legal, m, hs) & (req_view == want_view)
    is_upgrade_race = active & (m == int(MsgType.REQ_UPGRADE)) & \
        (req_view != int(RemoteView.S))
    do = active & legal

    val = home_value(st)                                  # serve-then-move
    new_home = lookup(tables.grant_new_home, m, hs)
    resp = lookup(tables.grant_resp, m, hs)
    wb = lookup(tables.grant_wb, m, hs)

    if tables.stateless_home:
        # single joint state I*: serve the data, record nothing.
        backing, home_state, view = st.backing, st.home_state, st.view
    else:
        backing = jnp.where((do & wb)[..., None], st.home_buf, st.backing)
        home_state = jnp.where(do, new_home.astype(jnp.int8),
                               st.home_state)
        new_view = lookup(tables.grant_view, m)
        if st.view.dtype == jnp.uint32:
            # set/clear exactly the requester's bit on granting lines —
            # the [..., R, L] one-hot compare becomes two word updates.
            nv = new_view.astype(jnp.int32)
            pres = st.view[..., PLANE_PRES, :, :]
            excl = st.view[..., PLANE_EXCL, :, :]
            pres2 = write_bit(pres, do & (nv != int(RemoteView.I)),
                              do & (nv == int(RemoteView.I)), node)
            excl2 = write_bit(excl, do & (nv == int(RemoteView.EM)),
                              do & (nv != int(RemoteView.EM)), node)
            view = jnp.stack([pres2, excl2], axis=-3)
        else:
            R = st.view.shape[-2]
            onehot = jnp.arange(R)[:, None] == node[..., None, :]
            view = jnp.where(onehot & do[..., None, :],
                             new_view[..., None, :].astype(jnp.int8),
                             st.view)

    resp = jnp.where(do, resp.astype(jnp.int8), jnp.int8(int(MsgType.NOP)))
    resp = jnp.where(is_upgrade_race, jnp.int8(int(MsgType.RESP_NACK)), resp)
    bad = active & ~legal & ~is_upgrade_race
    new = st._replace(home_state=home_state, view=view, backing=backing,
                      illegal=st.illegal + bad.sum().astype(jnp.int32))
    return new, resp, val


def home_apply_write(st: DirectoryMNState, mask: jnp.ndarray,
                     value: jnp.ndarray) -> DirectoryMNState:
    """Home-side writes for ``mask`` lines (preconditions: all views I)."""
    has = st.home_state != int(HomeState.I)
    wb = mask & has
    direct = mask & ~has
    return st._replace(
        home_buf=jnp.where(wb[..., None], value, st.home_buf),
        home_state=jnp.where(wb, jnp.int8(int(HomeState.M)), st.home_state),
        backing=jnp.where(direct[..., None], value, st.backing),
    )
