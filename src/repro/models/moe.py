"""Mixture-of-Experts FFN with capacity-based expert-parallel dispatch.

granite-3.0-moe (32e top-8) and qwen3-moe (128e top-8) use this block.

Dispatch is the static-shape sort/scatter formulation: tokens pick top-k
experts; each (token, k) slot scatters into a per-expert capacity buffer
``(E, C, d)``; expert FFNs run as batched einsums with the expert dimension
sharded over the ``model`` mesh axis (EP) — XLA inserts the all-to-all
equivalents at the resharding boundary.  Overflow beyond capacity is dropped
(standard capacity-factor semantics); the router carries the usual
load-balancing auxiliary loss.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import Params, dense_init, qeinsum, rms_norm

#: Sharding constraint for the (E, C, d) expert buffers (set by the launch
#: builders: NamedSharding(mesh, P("model", None, None))).  Pinning the
#: QUANTIZED buffer to the expert sharding forces the int8 payload — not
#: the dequantized bf16 — across the EP all-to-all boundary.
_EP_SPEC = None


def set_ep_spec(spec) -> None:
    global _EP_SPEC
    _EP_SPEC = spec


def _constrain_ep(x):
    if _EP_SPEC is None:
        return x
    try:
        return jax.lax.with_sharding_constraint(x, _EP_SPEC)
    except (RuntimeError, ValueError):
        return x


# --- int8 dispatch/combine with custom VJP ---------------------------------
# int arrays carry no tangents, so the int8 wire path needs explicit
# gradients: forward moves int8 + per-slot scales across the EP boundary;
# backward moves the bf16 cotangent through the transposed gather/scatter
# (backward traffic uncompressed — accounted in roofline.analytic).


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _dispatch_q8(src, shape_ec, flat_e, safe_pos, keep):
    """src [T*k, d] -> bf16 buffer [E, C, d] via an int8 wire."""
    E, C = shape_ec
    d = src.shape[-1]
    s_scale = jnp.maximum(jnp.max(jnp.abs(
        src.astype(jnp.float32)), axis=-1), 1e-9) / 127.0
    src_q = jnp.clip(jnp.round(src.astype(jnp.float32)
                               / s_scale[:, None]), -127, 127
                     ).astype(jnp.int8)
    buf_q = jnp.zeros((E, C, d), jnp.int8).at[flat_e, safe_pos].add(
        jnp.where(keep[:, None], src_q, 0))
    buf_s = jnp.zeros((E, C), jnp.float32).at[flat_e, safe_pos].add(
        jnp.where(keep, s_scale, 0))
    buf_q = _constrain_ep(buf_q)          # int8 crosses the EP boundary
    return buf_q.astype(src.dtype) * buf_s[..., None].astype(src.dtype)


def _dispatch_q8_fwd(src, shape_ec, flat_e, safe_pos, keep):
    return _dispatch_q8(src, shape_ec, flat_e, safe_pos, keep), \
        (flat_e, safe_pos, keep)


def _dispatch_q8_bwd(shape_ec, res, g):
    flat_e, safe_pos, keep = res
    g_src = jnp.where(keep[:, None], g[flat_e, safe_pos], 0)
    return g_src, None, None, None


_dispatch_q8.defvjp(_dispatch_q8_fwd, _dispatch_q8_bwd)


@jax.custom_vjp
def _combine_q8(out_buf, flat_e, safe_pos, keep):
    """out_buf [E, C, d] -> slot rows [T*k, d] via an int8 wire."""
    o_scale = jnp.maximum(jnp.max(jnp.abs(
        out_buf.astype(jnp.float32)), axis=-1), 1e-9) / 127.0
    out_q = jnp.clip(jnp.round(out_buf.astype(jnp.float32)
                               / o_scale[..., None]), -127, 127
                     ).astype(jnp.int8)
    out_q = _constrain_ep(out_q)
    slot_q = out_q[flat_e, safe_pos]
    slot_s = o_scale[flat_e, safe_pos]
    out = slot_q.astype(out_buf.dtype) * slot_s[:, None].astype(
        out_buf.dtype)
    return jnp.where(keep[:, None], out, 0)


def _combine_q8_fwd(out_buf, flat_e, safe_pos, keep):
    return _combine_q8(out_buf, flat_e, safe_pos, keep), \
        (out_buf.shape, flat_e, safe_pos, keep)


def _combine_q8_bwd(res, g):
    shape, flat_e, safe_pos, keep = res
    g_buf = jnp.zeros(shape, g.dtype).at[flat_e, safe_pos].add(
        jnp.where(keep[:, None], g, 0))
    return g_buf, None, None, None


_combine_q8.defvjp(_combine_q8_fwd, _combine_q8_bwd)


def moe_params(key, cfg: ModelConfig, dtype) -> Params:
    m = cfg.moe
    d, f, e = cfg.d_model, m.expert_d_ff, m.n_experts
    ks = jax.random.split(key, 4)
    return {
        "ln": jnp.zeros((d,), dtype),
        "router": dense_init(ks[0], d, (d, e), jnp.float32),
        "w1": dense_init(ks[1], d, (e, d, f), dtype),
        "w3": dense_init(ks[2], d, (e, d, f), dtype),
        "w2": dense_init(ks[3], f, (e, f, d), dtype),
    }


def moe_block_local(p: Params, cfg: ModelConfig, x: jnp.ndarray, mesh,
                    dp_axes) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Shard-LOCAL MoE dispatch (the §Perf cell-A fix): each DP shard
    routes only its own tokens into per-shard capacity buffers against
    (gathered) expert weights — no cross-device traffic from the dispatch
    scatter at all.  This is what the naive jit remap could not express
    (its global-cumsum capacity positions globalized the scatter; caught
    by the HLO verification, see EXPERIMENTS.md §Perf cell A iter 4/5).

    Capacity semantics change slightly (per-shard capacity instead of
    global), which is standard for shard-local MoE (e.g. MaxText).
    """
    from jax.sharding import PartitionSpec as P

    def local(p_, x_):
        y, aux = moe_block(p_, cfg, x_)
        return y, jax.lax.pmean(aux, dp_axes)

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(), P(dp_axes, None, None)),
                       out_specs=(P(dp_axes, None, None), P()),
                       check_vma=False)
    y, aux = fn(p, x)
    return y, aux


def moe_block(p: Params, cfg: ModelConfig, x: jnp.ndarray
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: [B, S, d] -> (y, aux_loss)."""
    m = cfg.moe
    B, S, d = x.shape
    n_tok = B * S
    xn = rms_norm(x, p["ln"]).reshape(n_tok, d)

    gate_logits = xn.astype(jnp.float32) @ p["router"]        # [T, E]
    probs = jax.nn.softmax(gate_logits, axis=-1)
    gate_w, expert_idx = jax.lax.top_k(probs, m.top_k)        # [T, k]
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch-style: E * sum_e f_e * p_e)
    me = probs.mean(axis=0)
    ce = jnp.zeros((m.n_experts,), jnp.float32).at[expert_idx.reshape(-1)
                                                   ].add(1.0) / (n_tok * m.top_k)
    aux = m.n_experts * jnp.sum(me * ce) * m.router_aux_weight

    # capacity buffers
    cap = int(n_tok * m.top_k / m.n_experts * m.capacity_factor)
    cap = max(cap, m.top_k)
    flat_e = expert_idx.reshape(-1)                            # [T*k]
    # position of each slot within its expert (by arrival order)
    onehot = jax.nn.one_hot(flat_e, m.n_experts, dtype=jnp.int32)
    pos = (jnp.cumsum(onehot, axis=0) - onehot)[jnp.arange(n_tok * m.top_k),
                                                flat_e]
    keep = pos < cap
    safe_pos = jnp.where(keep, pos, cap - 1)

    # scatter tokens into (E, C, d)
    tok_of_slot = jnp.repeat(jnp.arange(n_tok), m.top_k)
    src = jnp.where(keep[:, None], xn[tok_of_slot], 0)
    if m.dispatch_int8:
        # §Perf: the buffer that crosses the EP all-to-all is int8 with a
        # per-slot scale (d+4 bytes/slot instead of 2d) — halves the wire
        # bytes of the dominant collective.  Dequantized at the expert.
        buf = _dispatch_q8(src, (m.n_experts, cap), flat_e, safe_pos, keep)
    else:
        buf = jnp.zeros((m.n_experts, cap, d), x.dtype
                        ).at[flat_e, safe_pos].add(src)
        buf = _constrain_ep(buf)

    # expert FFN (swiglu), E sharded over the model axis (EP)
    h = qeinsum("ecd,edf->ecf", buf, p["w1"])
    g = qeinsum("ecd,edf->ecf", buf, p["w3"])
    h = jax.nn.silu(h.astype(jnp.float32)).astype(x.dtype) * g
    out_buf = qeinsum("ecf,efd->ecd", h, p["w2"])           # [E, C, d]

    if m.dispatch_int8:
        # combine direction: quantize expert-side, gather int8, dequant.
        slot_out = _combine_q8(out_buf, flat_e, safe_pos, keep)
    else:
        slot_out = out_buf[flat_e, safe_pos]                # [T*k, d]
    slot_out = jnp.where(keep[:, None], slot_out, 0)
    slot_w = gate_w.reshape(-1).astype(x.dtype)
    y = jnp.zeros((n_tok, d), x.dtype).at[tok_of_slot].add(
        slot_out * slot_w[:, None])
    return x + y.reshape(B, S, d), aux
