"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

Each function is the semantic ground truth its kernel is tested against
(``tests/test_kernels.py`` sweeps shapes/dtypes and asserts allclose).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# select_scan: predicate + in-block compaction (paper Fig. 5 operator)
# ---------------------------------------------------------------------------


def select_scan_ref(table: jnp.ndarray, x: float, y: float,
                    block_rows: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Blockwise SELECT: for each block of ``block_rows`` rows, matches are
    compacted to the front of the block (zeros after).

    Returns (packed [n_blocks, block_rows, width], counts [n_blocks]).
    """
    n, w = table.shape
    assert n % block_rows == 0
    blocks = table.reshape(n // block_rows, block_rows, w)

    def per_block(blk):
        mask = (blk[:, 0] > x) & (blk[:, 1] < y)
        count = mask.sum(dtype=jnp.int32)
        order = jnp.argsort(jnp.where(mask, 0, 1), stable=True)
        packed = jnp.where((jnp.arange(block_rows) < count)[:, None],
                           blk[order], 0)
        return packed, count

    return jax.vmap(per_block)(blocks)


# ---------------------------------------------------------------------------
# regex_dfa: table-driven DFA over byte strings (paper Fig. 7 operator)
# ---------------------------------------------------------------------------


def regex_dfa_ref(trans: jnp.ndarray, accept: jnp.ndarray,
                  strings: jnp.ndarray) -> jnp.ndarray:
    """[rows] bool: absorbing-accept DFA over NUL-padded rows."""
    state = jnp.zeros((strings.shape[0],), jnp.int32)

    def step(state, chars):
        return trans[state, chars.astype(jnp.int32)], None

    final, _ = jax.lax.scan(step, state, strings.T)
    return accept[final]


# ---------------------------------------------------------------------------
# hash_probe: chained hash-table probe (paper Fig. 6 operator)
# ---------------------------------------------------------------------------


def hash_probe_ref(heads: jnp.ndarray, keys: jnp.ndarray, nxt: jnp.ndarray,
                   queries: jnp.ndarray, max_chain: int
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (found_idx [q] int32 (-1 = miss), steps [q] int32)."""
    n_buckets = heads.shape[0]
    h = (queries.astype(jnp.uint32) * jnp.uint32(2654435769)) >> jnp.uint32(16)
    ptr = heads[(h % jnp.uint32(n_buckets)).astype(jnp.int32)]
    found = jnp.full_like(ptr, -1)
    steps = jnp.zeros_like(ptr)
    for _ in range(max_chain):
        live = (ptr >= 0) & (found < 0)
        safe = jnp.maximum(ptr, 0)
        hit = live & (keys[safe] == queries.astype(jnp.uint32))
        found = jnp.where(hit, ptr, found)
        steps = steps + live.astype(jnp.int32)
        ptr = jnp.where(live & ~hit, nxt[safe], ptr)
    return found, steps


# ---------------------------------------------------------------------------
# flash_attention: blocked attention w/ GQA, causal, window, logit softcap
# ---------------------------------------------------------------------------


def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        causal: bool = True,
                        window: Optional[int] = None,
                        softcap: Optional[float] = None,
                        scale: Optional[float] = None,
                        kv_length=None) -> jnp.ndarray:
    """Dense-softmax oracle.

    q: [B, Hq, Sq, D]; k, v: [B, Hkv, Skv, D] with Hq % Hkv == 0 (GQA).
    window: local attention — key j visible from query i iff i-j < window.
    softcap: gemma2-style ``cap * tanh(logits / cap)``.
    kv_length: (traced) number of valid KV positions — the decode path's
    cache occupancy; queries sit at the END of the valid region.
    """
    B, Hq, Sq, D = q.shape
    Hkv = k.shape[1]
    rep = Hq // Hkv
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    scale = scale if scale is not None else D ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    Skv = k.shape[2]
    valid = jnp.asarray(Skv if kv_length is None else kv_length, jnp.int32)
    qi = jnp.arange(Sq)[:, None] + (valid - Sq)  # queries end-aligned
    kj = jnp.arange(Skv)[None, :]
    mask = kj < valid
    if causal:
        mask &= kj <= qi
    if window is not None:
        mask &= (qi - kj) < window
    logits = jnp.where(mask[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)
                      ).astype(v.dtype)


def chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      causal: bool = True,
                      window: Optional[int] = None,
                      softcap: Optional[float] = None,
                      kv_length=None,
                      chunk_q: int = 512, chunk_k: int = 1024
                      ) -> jnp.ndarray:
    """Flash-style double-chunked attention in pure jnp + lax.scan.

    This is what the production step functions COMPILE (the Pallas kernel
    is the TPU-native version of the same schedule): memory is bounded by
    one (chunk_q x chunk_k) tile per (batch, head), never the full
    [Sq, Skv] matrix.  GQA is handled by folding the head-repeat factor
    into the q tensor so KV is never materialized repeated.

    The q-chunk loop body is rematerialized (jax.checkpoint) so AD carries
    only the online-softmax state between chunks.
    """
    B, Hq, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    rep = Hq // Hkv
    cq = min(chunk_q, Sq)
    ck = min(chunk_k, Sk)
    # fall back to dense for ragged shapes (tiny cases / smoke tests).
    if Sq % cq or Sk % ck:
        return flash_attention_ref(q, k, v, causal=causal, window=window,
                                   softcap=softcap, kv_length=kv_length)
    nq, nk = Sq // cq, Sk // ck
    scale = D ** -0.5
    valid = jnp.asarray(Sk if kv_length is None else kv_length, jnp.int32)

    # [B, Hkv, rep, Sq, D] view of q; KV stays un-repeated.
    q5 = q.reshape(B, Hkv, rep, Sq, D)
    qs = q5.reshape(B, Hkv, rep, nq, cq, D).transpose(3, 0, 1, 2, 4, 5)
    ks = k.reshape(B, Hkv, nk, ck, D).transpose(2, 0, 1, 3, 4)
    vs = v.reshape(B, Hkv, nk, ck, D).transpose(2, 0, 1, 3, 4)

    def q_block(_, qi_blk):
        qi, qb = qi_blk          # qb: [B, Hkv, rep, cq, D]
        q_pos = qi * cq + jnp.arange(cq) + (valid - Sq)

        def kv_block(carry, kj_blk):
            m, l, acc = carry
            kj, kb, vb = kj_blk
            k_pos = kj * ck + jnp.arange(ck)
            lg = jnp.einsum("bhrqd,bhkd->bhrqk", qb.astype(jnp.float32),
                            kb.astype(jnp.float32)) * scale
            if softcap is not None:
                lg = softcap * jnp.tanh(lg / softcap)
            mask = (k_pos < valid)[None, :]
            if causal:
                mask = mask & (k_pos[None, :] <= q_pos[:, None])
            if window is not None:
                mask = mask & ((q_pos[:, None] - k_pos[None, :]) < window)
            lg = jnp.where(mask[None, None, None], lg, -1e30)
            m2 = jnp.maximum(m, lg.max(axis=-1))
            alpha = jnp.exp(m - m2)
            p = jnp.exp(lg - m2[..., None])
            dead = m2 <= -1e29
            p = jnp.where(dead[..., None], 0.0, p)
            alpha = jnp.where(dead, 1.0, alpha)
            l2 = l * alpha + p.sum(axis=-1)
            acc2 = (acc * alpha[..., None]
                    + jnp.einsum("bhrqk,bhkd->bhrqd", p,
                                 vb.astype(jnp.float32)))
            return (m2, l2, acc2), None

        init = (jnp.full((B, Hkv, rep, cq), -1e30, jnp.float32),
                jnp.zeros((B, Hkv, rep, cq), jnp.float32),
                jnp.zeros((B, Hkv, rep, cq, D), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(
            kv_block, init, (jnp.arange(nk), ks, vs))
        out = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(jax.checkpoint(q_block), None,
                           (jnp.arange(nq), qs))
    # outs: [nq, B, Hkv, rep, cq, D] -> [B, Hq, Sq, D]
    out = outs.transpose(1, 2, 3, 0, 4, 5).reshape(B, Hq, Sq, D)
    return out


# ---------------------------------------------------------------------------
# coherency_step: the coherency engine's per-step inner plane
# (core/engine_mn.py hot path).  These refs are the EXACT jnp expressions
# the engine's default XLA backend runs — all-integer/boolean arithmetic,
# so the kernel contract is BIT-EXACT equality, not allclose
# (tests/test_coherency_kernels.py).
# ---------------------------------------------------------------------------


def credit_rank_ref(active: jnp.ndarray, cand: jnp.ndarray) -> jnp.ndarray:
    """[..., L] int32 parity-split credit rank (``transport.credit_accept``).

    For each leading-axis initiator row: a candidate's rank against its
    odd/even VC is the VC's current occupancy plus the number of EARLIER
    candidates (stable line order) on the same parity.  The acceptance
    test is then ``cand & (rank < credits[vc])``, applied by the caller.
    """
    L = active.shape[-1]
    odd = (jnp.arange(L) & 1).astype(bool)
    c_o = jnp.where(odd, cand, False).astype(jnp.int32)
    c_e = jnp.where(odd, False, cand).astype(jnp.int32)
    occ_o = jnp.where(odd, active, False).sum(-1, keepdims=True)
    occ_e = jnp.where(odd, False, active).sum(-1, keepdims=True)
    rank_o = jnp.cumsum(c_o, axis=-1) - c_o
    rank_e = jnp.cumsum(c_e, axis=-1) - c_e
    return jnp.where(odd, occ_o + rank_o, occ_e + rank_e)


def arb_winner_ref(ready_all: jnp.ndarray, arb_rr: jnp.ndarray
                   ) -> jnp.ndarray:
    """[..., L] int32 rotating-priority winner select (``step_mn`` phase 4).

    ``ready_all`` is ``[..., P, L]`` over the P = R+1 arbitration
    participants (R remotes + the home); ``arb_rr`` is the per-line
    rotating pointer.  Participant p's priority on a line is
    ``(p - arb_rr) % P``; the winner is the ready participant of minimum
    priority (ties — only the not-ready fill value P — resolve to the
    LOWEST participant id, matching ``jnp.argmin``'s first-minimum rule).
    """
    P = ready_all.shape[-2]
    prio = (jnp.arange(P)[:, None] - arb_rr[..., None, :]) % P
    return jnp.argmin(jnp.where(ready_all, prio, P), axis=-2)


def count_fold_ref(mask: jnp.ndarray, msg: jnp.ndarray,
                   has_payload: jnp.ndarray
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Delivered-message fold (``engine._count``): one-hot compare +
    reduce over ALL leading axes.  Returns (delta [16] int32, payload
    delta [] int32) — the caller accumulates."""
    eq = msg.astype(jnp.int32)[..., None] == jnp.arange(16)
    axes = tuple(range(eq.ndim - 1))
    return ((eq & mask[..., None]).sum(axes),
            (mask & has_payload).sum())


def lat_hist_ref(lat: jnp.ndarray, retired: jnp.ndarray,
                 edges: Tuple[int, ...]) -> jnp.ndarray:
    """[R, NB] int32 retirement-latency histogram delta
    (``traffic.counters.update_counters``): bucket i holds lat in
    [edge[i-1], edge[i]), last bucket overflows; only ``retired`` lanes
    count.  ``searchsorted(edges, lat, side='right')`` is exactly
    ``sum_e (lat >= e)`` for sorted integer edges."""
    e = jnp.asarray(edges, jnp.int32)
    nb = len(edges) + 1
    bucket = (lat[..., None] >= e).sum(-1)
    onehot = bucket[..., None] == jnp.arange(nb)
    return (onehot & retired[..., None]).sum(axis=1)


def packed_any_ref(words: jnp.ndarray) -> jnp.ndarray:
    """[..., L] bool — any bit set per line of a packed ``[..., L, W]``
    uint32 plane (``directory_mn.any_bits``: the packed ``no_sharers`` /
    pending-home-request reductions)."""
    return (words != 0).any(axis=-1)


def packed_fanout_ref(pres: jnp.ndarray, excl: jnp.ndarray,
                      node: jnp.ndarray, shared_req: jnp.ndarray,
                      excl_req: jnp.ndarray
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Packed fan-out target sets (``directory_mn.needed_words``).

    ``pres``/``excl`` are the ``[..., L, W]`` presence/exclusive word
    planes, ``node`` the per-line winning requester id, ``shared_req`` /
    ``excl_req`` the per-line request-kind masks.  Returns
    ``(recall_w, inval_w)`` word planes: recall (HOME_DOWNGRADE_S) goes
    to EM holders other than the requester on a shared read; invalidate
    (HOME_DOWNGRADE_I) to all non-I holders other than the requester on
    an exclusive/upgrade request — one AND-NOT-hot per plane instead of
    an ``[R, L]`` one-hot compare.
    """
    W = pres.shape[-1]
    sel = jnp.arange(W) == (node // 32)[..., None]
    hot = jnp.where(
        sel, jnp.uint32(1) << (node % 32).astype(jnp.uint32)[..., None],
        jnp.uint32(0))
    recall_w = jnp.where(shared_req[..., None], excl & ~hot,
                         jnp.uint32(0))
    inval_w = jnp.where(excl_req[..., None], pres & ~hot, jnp.uint32(0))
    return recall_w, inval_w


# ---------------------------------------------------------------------------
# rglru_scan: RG-LRU gated linear recurrence (recurrentgemma)
# ---------------------------------------------------------------------------


def rglru_scan_ref(x: jnp.ndarray, a: jnp.ndarray,
                   h0: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * x_t   (per channel).

    x, a: [B, S, D]; returns h: [B, S, D].  The sqrt(1-a^2) input scaling is
    the RG-LRU normalization (arXiv:2402.19427 eq. 4).
    """
    beta = jnp.sqrt(jnp.maximum(1.0 - a.astype(jnp.float32) ** 2, 0.0))
    gx = beta * x.astype(jnp.float32)
    init = (jnp.zeros_like(x[:, 0], dtype=jnp.float32) if h0 is None
            else h0.astype(jnp.float32))

    def step(h, inp):
        at, gxt = inp
        h = at * h + gxt
        return h, h

    _, hs = jax.lax.scan(step, init,
                         (a.astype(jnp.float32).swapaxes(0, 1),
                          gx.swapaxes(0, 1)))
    return hs.swapaxes(0, 1).astype(x.dtype)
