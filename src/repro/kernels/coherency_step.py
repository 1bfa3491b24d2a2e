"""Pallas kernels for the coherency engine's per-step inner plane.

The four patterns XLA:CPU lowers worst in the ``EngineMN`` hot path (see
docs/perf.md), each as a Pallas kernel with its pure-jnp oracle in
``ref.py`` (the ops/ref contract of this package):

* ``credit_rank``  — parity-split credit ranking
  (``transport.credit_accept``): per initiator row, occupancy + earlier-
  candidate rank against the line's odd/even VC.
* ``arb_winner``   — per-line rotating-priority arbitration winner select
  (``core.engine_mn.step_mn`` phase 4) over the ``[P, L]`` participant
  plane (P = R remotes + the home).
* ``count_fold``   — the delivered-message one-hot counter fold
  (``core.engine._count``; the former ~45%-of-step scatter).
* ``lat_hist``     — the retirement-latency histogram fold
  (``traffic.counters.update_counters``).
* ``packed_any`` / ``packed_fanout`` — the bit-packed directory-plane
  reductions (``core.directory_mn`` under ``EngineConfig.packed``):
  per-line any-sharer via popcount over the ``[L, W]`` uint32 word
  plane, and the recall/invalidate fan-out sets as one AND-NOT-hot per
  plane.

Everything here is integer/boolean arithmetic, so the contract with the
refs is BIT-EXACT equality — in interpret mode on CPU (what CI runs) and
under real Mosaic lowering on TPU (``tests/test_tpu_compile.py`` compiles
every kernel for a TPU v5e at R=48, L=131072).  What Mosaic accepts
shapes the kernels:

* the line axis is tiled in lane-aligned blocks (multiples of 128) and
  no kernel holds a full-L block, so VMEM use does not grow with L.  A
  reduction that crosses line blocks carries its partial result over an
  "arbitrary" grid axis, in a resident output tile or a VMEM scratch;
* no integer matmul (v5e's MXU takes none): the credit rank's in-block
  running count is a bf16 matmul of a 0/1 plane against a 0/1 triangle,
  accumulated in f32 — exact, since no block sum exceeds the block width;
* no in-kernel reshape: one-hot folds compare against each type in a
  static unroll.  Argmin becomes an encode/min/decode over
  ``score * (P+1) + p`` (exact because priorities are a permutation per
  line and ties only occur at the not-ready fill value, where
  min-of-encoding picks the lowest participant id — the same
  first-minimum rule as ``jnp.argmin``), and ``searchsorted`` becomes a
  static unrolled ``sum(lat >= edge)``.

The engine reaches these only when its ``kernel_backend`` is "pallas"
(``REPRO_KERNEL_BACKEND`` env or ``EngineConfig.kernel_backend``); the
default backend keeps the original XLA expressions, bit-identical to
every committed baseline.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128
#: elements per [rows, lanes] block: each int32 temporary of a kernel
#: stays near 512 KiB, far inside the scoped VMEM of a TPU core.
_BLOCK_ELEMS = 1 << 17
#: row-block cap for [rows, L] planes (a multiple of every dtype's
#: sublane tile).
_ROW_CAP = 256


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _pad_rows(x: jnp.ndarray, mult: int):
    n = x.shape[0]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    width = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, width), n


def _pad_to(x: jnp.ndarray, shape) -> jnp.ndarray:
    """Zero/False-pad ``x`` up to ``shape`` at the high end of each axis
    (padding lanes are inert in every kernel: no activity, no ready)."""
    if tuple(x.shape) == tuple(shape):
        return x
    return jnp.pad(x, [(0, s - d) for s, d in zip(shape, x.shape)])


def _row_block(rows: int) -> Tuple[int, int]:
    """(row block, padded rows) for a ``[rows, L]`` plane."""
    bn = min(_round_up(max(rows, 1), 8), _ROW_CAP)
    return bn, _round_up(max(rows, 1), bn)


def _lane_block(rows: int, L: int, cap: int = 2048) -> Tuple[int, int]:
    """(lane block, padded L): a multiple of 128 lanes, at most ``cap``,
    sized so ``rows x lanes`` stays within ``_BLOCK_ELEMS``."""
    Lp = _round_up(max(L, 1), _LANE)
    fit = max(_LANE, _BLOCK_ELEMS // max(rows, 1) // _LANE * _LANE)
    bl = min(Lp, cap, fit)
    return bl, _round_up(Lp, bl)


def _params(*semantics: str):
    return pltpu.CompilerParams(dimension_semantics=semantics)


def _sum_lanes(x: jnp.ndarray) -> jnp.ndarray:
    """[bn, bl] int32 -> [bn, 1] row sums."""
    return jnp.sum(x, axis=1, keepdims=True)


def _flat_rows(x: jnp.ndarray) -> jnp.ndarray:
    """``[..., L]`` -> ``[rows, L]`` (rows = product of the leading axes)."""
    return x.reshape(math.prod(x.shape[:-1]), x.shape[-1])


# ---------------------------------------------------------------------------
# credit_rank
# ---------------------------------------------------------------------------


def _credit_rank_kernel(tri_ref, act_ref, cand_ref, out_ref,
                        occ_o, occ_e, run_o, run_e):
    # grid (row block, phase, line block): phase 0 folds the row's
    # parity-split occupancy over every line block; phase 1 writes the
    # ranks, carrying the running candidate count across line blocks.
    phase, lb = pl.program_id(1), pl.program_id(2)
    bn, bl = act_ref.shape
    odd = (jax.lax.broadcasted_iota(jnp.int32, (bn, bl), 1) & 1) == 1

    def parity_counts(x):                                 # [bn, _LANE] x2
        o = _sum_lanes(jnp.where(x & odd, 1, 0))
        e = _sum_lanes(jnp.where(x & ~odd, 1, 0))
        return (jnp.broadcast_to(o, (bn, _LANE)),
                jnp.broadcast_to(e, (bn, _LANE)))

    @pl.when((phase == 0) & (lb == 0))
    def _():
        occ_o[:] = jnp.zeros_like(occ_o)
        occ_e[:] = jnp.zeros_like(occ_e)

    @pl.when(phase == 0)
    def _():
        o, e = parity_counts(act_ref[:])
        occ_o[:] += o
        occ_e[:] += e

    @pl.when((phase == 1) & (lb == 0))
    def _():
        run_o[:] = jnp.zeros_like(run_o)
        run_e[:] = jnp.zeros_like(run_e)

    @pl.when(phase == 1)
    def _():
        cand = cand_ref[:]
        # earlier same-parity candidates within the block: a 0/1 bf16
        # plane against the 0/1 (j < i, same parity) triangle, summed in
        # f32 — exact (at most ``bl`` ones), and the MXU takes it.
        c = jnp.where(cand, 1.0, 0.0).astype(jnp.bfloat16)
        local = jnp.dot(c, tri_ref[:],
                        preferred_element_type=jnp.float32
                        ).astype(jnp.int32)
        base = jnp.where(odd, occ_o[:, :1] + run_o[:, :1],
                         occ_e[:, :1] + run_e[:, :1])
        out_ref[:] = base + local
        o, e = parity_counts(cand)
        run_o[:] += o
        run_e[:] += e


def credit_rank(active: jnp.ndarray, cand: jnp.ndarray, *,
                interpret=None) -> jnp.ndarray:
    """[..., L] int32 — Pallas twin of ``ref.credit_rank_ref``."""
    shape = active.shape
    rows, L = math.prod(shape[:-1]), shape[-1]
    bn, rows_p = _row_block(rows)
    bl, Lp = _lane_block(bn, L, cap=512)
    nl = Lp // bl
    act2 = _pad_to(_flat_rows(active), (rows_p, Lp))
    cnd2 = _pad_to(_flat_rows(cand), (rows_p, Lp))
    # blocks start at even lines, so in-block parity is line parity.
    j = jnp.arange(bl)[:, None]                           # source line
    i = jnp.arange(bl)[None, :]                           # ranked line
    tri = ((j < i) & (((j ^ i) & 1) == 0)).astype(jnp.bfloat16)
    out = pl.pallas_call(
        _credit_rank_kernel,
        grid=(rows_p // bn, 2, nl),
        in_specs=[
            pl.BlockSpec((bl, bl), lambda b, ph, l: (0, 0)),
            # phase 0 walks ``active``; phase 1 parks it on its last block
            # (no refetch) and walks ``cand`` instead.
            pl.BlockSpec((bn, bl),
                         lambda b, ph, l: (b, l * (1 - ph) + (nl - 1) * ph)),
            pl.BlockSpec((bn, bl), lambda b, ph, l: (b, l * ph)),
        ],
        # the output block stays on line block 0 through phase 0 (never
        # written there) and is first written back once phase 1 moves on.
        out_specs=pl.BlockSpec((bn, bl), lambda b, ph, l: (b, l * ph)),
        out_shape=jax.ShapeDtypeStruct((rows_p, Lp), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bn, _LANE), jnp.int32)] * 4,
        compiler_params=_params("parallel", "arbitrary", "arbitrary"),
        interpret=_interpret() if interpret is None else interpret,
    )(tri, act2, cnd2)
    return out[:rows, :L].reshape(shape)


# ---------------------------------------------------------------------------
# arb_winner
# ---------------------------------------------------------------------------


def _arb_winner_kernel(ready_ref, rr_ref, out_ref, *, P: int):
    ready = ready_ref[0]                                  # [P, bl]
    rr = rr_ref[0]                                        # [1, bl] int32
    p = jax.lax.broadcasted_iota(jnp.int32, ready.shape, 0)
    prio = (p - rr) % P                                   # permutation/line
    score = jnp.where(ready, prio, P)
    # encode (score, participant) into one key: distinct ready scores
    # dominate; the only ties are at the fill score P, where min picks the
    # smallest p — jnp.argmin's first-minimum rule.
    enc = score * (P + 1) + p
    out_ref[0] = (jnp.min(enc, axis=0, keepdims=True) % (P + 1)
                  ).astype(jnp.int32)


def arb_winner(ready_all: jnp.ndarray, arb_rr: jnp.ndarray, *,
               interpret=None) -> jnp.ndarray:
    """[..., L] int32 — Pallas twin of ``ref.arb_winner_ref``.

    ``ready_all`` is ``[..., P, L]`` (P = R+1 participants), ``arb_rr``
    ``[..., L]``; leading axes (the multi-home fold's H) and line blocks
    form the grid.  The pointer rides as ``[n, 1, L]`` so its block's
    last two dims are (full, lane-aligned) at every H.
    """
    P, L = ready_all.shape[-2:]
    lead = ready_all.shape[:-2]
    n = math.prod(lead)
    bl, Lp = _lane_block(P, L)
    ready3 = _pad_to(ready_all.reshape(n, P, L), (n, P, Lp))
    rr3 = _pad_to(arb_rr.reshape(n, 1, L).astype(jnp.int32), (n, 1, Lp))
    out = pl.pallas_call(
        functools.partial(_arb_winner_kernel, P=P),
        grid=(n, Lp // bl),
        in_specs=[pl.BlockSpec((1, P, bl), lambda h, l: (h, 0, l)),
                  pl.BlockSpec((1, 1, bl), lambda h, l: (h, 0, l))],
        out_specs=pl.BlockSpec((1, 1, bl), lambda h, l: (h, 0, l)),
        out_shape=jax.ShapeDtypeStruct((n, 1, Lp), jnp.int32),
        compiler_params=_params("parallel", "parallel"),
        interpret=_interpret() if interpret is None else interpret,
    )(ready3, rr3)
    return out[:, 0, :L].reshape(lead + (L,))


# ---------------------------------------------------------------------------
# count_fold
# ---------------------------------------------------------------------------

#: message-type rows of the count_fold accumulator; row 16 is the payload
#: count.
_N_TYPES = 16


def _total(x: jnp.ndarray) -> jnp.ndarray:
    """[bn, bl] int32 -> [1, 1] block total."""
    return jnp.sum(_sum_lanes(x), axis=0, keepdims=True)


def _count_fold_kernel(msg_ref, mask_ref, pay_ref, out_ref):
    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    msg = msg_ref[:].astype(jnp.int32)                    # [bn, bl]
    mask = mask_ref[:]
    row = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
    upd = jnp.where(row == _N_TYPES,
                    _total(jnp.where(mask & pay_ref[:], 1, 0)), 0)
    for k in range(_N_TYPES):   # static one-hot unroll, no reshape
        upd = upd + jnp.where(
            row == k, _total(jnp.where(mask & (msg == k), 1, 0)), 0)
    out_ref[:] += upd


def count_fold(mask: jnp.ndarray, msg: jnp.ndarray,
               has_payload: jnp.ndarray, *,
               interpret=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(delta [16] int32, payload delta [] int32) — Pallas twin of
    ``ref.count_fold_ref``.  The grid walks ``[rows, L]`` blocks
    sequentially, accumulating into one resident output tile (padding
    lanes are masked off and add 0)."""
    shape = (1,) + msg.shape if msg.ndim < 2 else msg.shape
    rows, L = math.prod(shape[:-1]), shape[-1]
    bn, rows_p = _row_block(rows)
    bl, Lp = _lane_block(bn, L)
    flat = [_pad_to(_flat_rows(x.reshape(shape)), (rows_p, Lp))
            for x in (msg, mask, has_payload)]
    spec = pl.BlockSpec((bn, bl), lambda b, l: (b, l))
    out = pl.pallas_call(
        _count_fold_kernel,
        grid=(rows_p // bn, Lp // bl),
        in_specs=[spec] * 3,
        out_specs=pl.BlockSpec((_N_TYPES + 1, _LANE), lambda b, l: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((_N_TYPES + 1, _LANE), jnp.int32),
        compiler_params=_params("arbitrary", "arbitrary"),
        interpret=_interpret() if interpret is None else interpret,
    )(*flat)
    return out[:_N_TYPES, 0], out[_N_TYPES, 0]


# ---------------------------------------------------------------------------
# lat_hist
# ---------------------------------------------------------------------------


def _lat_hist_kernel(lat_ref, ret_ref, out_ref, *, edges: Tuple[int, ...]):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    lat = lat_ref[:]                                      # [bn, bl] int32
    ret = ret_ref[:]
    bucket = jnp.zeros_like(lat)
    for e in edges:     # static unroll == searchsorted(side="right")
        bucket = bucket + (lat >= e).astype(jnp.int32)
    col = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    upd = jnp.zeros(out_ref.shape, jnp.int32)
    for b in range(out_ref.shape[1]):
        upd = upd + jnp.where(
            col == b, _sum_lanes(jnp.where(ret & (bucket == b), 1, 0)), 0)
    out_ref[:] += upd


def lat_hist(lat: jnp.ndarray, retired: jnp.ndarray,
             edges: Tuple[int, ...], *, interpret=None) -> jnp.ndarray:
    """[R, NB] int32 — Pallas twin of ``ref.lat_hist_ref`` (2-D input).
    Each row block's histogram stays resident while the grid walks its
    line blocks."""
    R, L = lat.shape
    nb = len(edges) + 1
    bn, rows_p = _row_block(R)
    bl, Lp = _lane_block(bn, L)
    lat2 = _pad_to(lat.astype(jnp.int32), (rows_p, Lp))
    ret2 = _pad_to(retired, (rows_p, Lp))
    spec = pl.BlockSpec((bn, bl), lambda b, l: (b, l))
    out = pl.pallas_call(
        functools.partial(_lat_hist_kernel, edges=tuple(edges)),
        grid=(rows_p // bn, Lp // bl),
        in_specs=[spec, spec],
        out_specs=pl.BlockSpec((bn, nb), lambda b, l: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_p, nb), jnp.int32),
        compiler_params=_params("parallel", "arbitrary"),
        interpret=_interpret() if interpret is None else interpret,
    )(lat2, ret2)
    return out[:R]


# ---------------------------------------------------------------------------
# packed_any
# ---------------------------------------------------------------------------


def _packed_any_kernel(words_ref, out_ref):
    w = words_ref[:]                                      # [bn, W] uint32
    cnt = jax.lax.population_count(w).astype(jnp.int32)
    out_ref[:] = (cnt.sum(-1, keepdims=True) > 0).astype(jnp.int32)


def packed_any(words: jnp.ndarray, *, block_rows: int = 256,
               interpret=None) -> jnp.ndarray:
    """[..., L] bool — Pallas twin of ``ref.packed_any_ref``: per-line
    popcount-over-words > 0 on a packed ``[..., L, W]`` uint32 plane."""
    shape = words.shape
    W = shape[-1]
    rows = 1
    for d in shape[:-1]:
        rows *= d
    w2 = words.reshape(rows, W)
    bn = min(block_rows, max(rows, 1))
    w2, _ = _pad_rows(w2, bn)
    out = pl.pallas_call(
        _packed_any_kernel,
        grid=(w2.shape[0] // bn,),
        in_specs=[pl.BlockSpec((bn, W), lambda b: (b, 0))],
        out_specs=pl.BlockSpec((bn, 1), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((w2.shape[0], 1), jnp.int32),
        interpret=_interpret() if interpret is None else interpret,
    )(w2)
    return out[:rows, 0].reshape(shape[:-1]) != 0


# ---------------------------------------------------------------------------
# packed_fanout
# ---------------------------------------------------------------------------


def _packed_fanout_kernel(pres_ref, excl_ref, node_ref, sh_ref, ex_ref,
                          rec_ref, inv_ref, *, W: int):
    pres = pres_ref[:]                                    # [bn, W] uint32
    excl = excl_ref[:]
    node = node_ref[:]                                    # [bn, 1] int32
    widx = jax.lax.broadcasted_iota(jnp.int32, (pres.shape[0], W), 1)
    hot = jnp.where(widx == node // 32,
                    jnp.uint32(1) << (node % 32).astype(jnp.uint32),
                    jnp.uint32(0))
    rec_ref[:] = jnp.where(sh_ref[:], excl & ~hot, jnp.uint32(0))
    inv_ref[:] = jnp.where(ex_ref[:], pres & ~hot, jnp.uint32(0))


def packed_fanout(pres: jnp.ndarray, excl: jnp.ndarray,
                  node: jnp.ndarray, shared_req: jnp.ndarray,
                  excl_req: jnp.ndarray, *, block_rows: int = 256,
                  interpret=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(recall_w, inval_w) ``[..., L, W]`` uint32 — Pallas twin of
    ``ref.packed_fanout_ref`` (the packed directory fan-out sets)."""
    shape = pres.shape
    W = shape[-1]
    rows = 1
    for d in shape[:-1]:
        rows *= d
    p2 = pres.reshape(rows, W)
    e2 = excl.reshape(rows, W)
    n2 = node.reshape(rows, 1).astype(jnp.int32)
    s2 = shared_req.reshape(rows, 1)
    x2 = excl_req.reshape(rows, 1)
    bn = min(block_rows, max(rows, 1))
    p2, _ = _pad_rows(p2, bn)
    e2, _ = _pad_rows(e2, bn)
    n2, _ = _pad_rows(n2, bn)
    s2, _ = _pad_rows(s2, bn)
    x2, _ = _pad_rows(x2, bn)
    rec, inv = pl.pallas_call(
        functools.partial(_packed_fanout_kernel, W=W),
        grid=(p2.shape[0] // bn,),
        in_specs=[pl.BlockSpec((bn, W), lambda b: (b, 0)),
                  pl.BlockSpec((bn, W), lambda b: (b, 0)),
                  pl.BlockSpec((bn, 1), lambda b: (b, 0)),
                  pl.BlockSpec((bn, 1), lambda b: (b, 0)),
                  pl.BlockSpec((bn, 1), lambda b: (b, 0))],
        out_specs=[pl.BlockSpec((bn, W), lambda b: (b, 0)),
                   pl.BlockSpec((bn, W), lambda b: (b, 0))],
        out_shape=[jax.ShapeDtypeStruct((p2.shape[0], W), jnp.uint32),
                   jax.ShapeDtypeStruct((p2.shape[0], W), jnp.uint32)],
        interpret=_interpret() if interpret is None else interpret,
    )(p2, e2, n2, s2, x2)
    return rec[:rows].reshape(shape), inv[:rows].reshape(shape)
