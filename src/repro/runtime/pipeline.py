"""Pipeline parallelism (GPipe schedule) over a mesh axis via shard_map +
collective_permute.

Stages hold contiguous layer slices (the stacked-layer arrays are sharded
on their leading dim over the ``stage`` axis); microbatches stream through
with the canonical GPipe loop: at tick t, stage s processes microbatch
t - s, and activations hop stage->stage+1 with a collective_permute.  The
loop runs n_micro + n_stages - 1 ticks (the pipeline bubble); bubble
fraction = (S-1)/(M+S-1), reported by ``bubble_fraction``.

Used as an optional execution mode over the ``pod`` axis (layers split
across pods, DCN carries only boundary activations instead of gradient
all-reduce — the right trade when d_model * B is small vs param bytes).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipeline_apply(mesh: Mesh, axis: str, layer_fn: Callable,
                   stage_params, x_micro: jnp.ndarray) -> jnp.ndarray:
    """Run microbatches through pipeline stages.

    Args:
      layer_fn: (params_slice, x) -> x, the per-stage computation (a slice
        of stacked layers, itself typically a lax.scan).
      stage_params: stacked layer params, leading dim sharded over ``axis``.
      x_micro: [n_micro, mb, ...] microbatched activations (replicated in;
        the first stage consumes them in order).

    Returns [n_micro, mb, ...] outputs (from the last stage, gathered).
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    ticks = n_micro + n_stages - 1

    def stage_fn(params, xs):
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        sid = jax.lax.axis_index(axis)
        mb_shape = xs.shape[1:]
        buf = jnp.zeros_like(xs)            # completed outputs (last stage)

        def tick(t, carry):
            buf, inflight = carry
            # stage 0 ingests microbatch t (if any); others take inflight.
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            x_in = jnp.where(sid == 0, xs[mb_idx], inflight)
            y = layer_fn(params, x_in)
            # pass activations to the next stage.
            perm = [(i, i + 1) for i in range(n_stages - 1)]
            nxt = jax.lax.ppermute(y, axis, perm)
            # last stage completes microbatch t - (n_stages - 1).
            done_idx = t - (n_stages - 1)
            write = (sid == n_stages - 1) & (done_idx >= 0)
            buf = jax.lax.cond(
                write,
                lambda b: jax.lax.dynamic_update_index_in_dim(
                    b, y, jnp.maximum(done_idx, 0), 0),
                lambda b: b, buf)
            return buf, nxt

        init_inflight = jnp.zeros(mb_shape, xs.dtype)
        buf, _ = jax.lax.fori_loop(0, ticks, tick, (buf, init_inflight))
        # broadcast the last stage's buffer to all (psum of masked buf).
        buf = jax.lax.psum(
            jnp.where(sid == n_stages - 1, buf, jnp.zeros_like(buf)), axis)
        return buf

    fn = jax.shard_map(stage_fn, mesh=mesh,
                       in_specs=(P(axis), P()), out_specs=P(),
                       check_vma=False)
    return fn(stage_params, x_micro)
