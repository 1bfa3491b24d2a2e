"""Operator-pushdown collectives: run the operator at the data's home,
move only the matches (paper §3.4 + §5, Figs. 3/4).

The paper's economics: with operator pushdown the interconnect carries
``selectivity x table_bytes`` instead of ``table_bytes`` — the FPGA operator
is DRAM-bound whenever selectivity < link_bw / DRAM_bw (1:6 on Enzian).
These ``shard_map`` collectives express the same structure on a TPU mesh:
each *home shard* scans/probes/matches its resident rows (the NMP hot loop,
also available as Pallas kernels), and only compacted matches cross the
interconnect via ``all_gather`` — a "filter-before-gather" collective.

All outputs are fixed-capacity (static shapes) with explicit counts, the
FIFO-with-occupancy structure of the paper's operator interface (Fig. 3).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..nmp.dfa import dfa_select
from ..nmp.kvstore import KVStore, fib_hash
from ..nmp.regex import DFA
from ..nmp.select import select_scan


class PushdownResult(NamedTuple):
    """Fixed-capacity gathered matches + per-shard counts + byte accounting."""

    rows: jnp.ndarray        # [n_shards, capacity, row_width]
    counts: jnp.ndarray      # [n_shards] int32
    moved_rows: jnp.ndarray  # [] int32 — rows that crossed the interconnect


def _gather_matches(axis: str, packed: jnp.ndarray, count: jnp.ndarray
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    counts = jax.lax.all_gather(count, axis)
    packs = jax.lax.all_gather(packed, axis)
    return packs, counts


def pushdown_select(mesh: Mesh, axis: str, capacity: int,
                    table: jnp.ndarray, x, y) -> PushdownResult:
    """Distributed SELECT: each home shard filters its rows, matches are
    gathered.  ``table`` is sharded [rows, width] over ``axis``."""

    def shard_fn(tbl, xx, yy):
        packed, count, _ = select_scan(tbl, xx, yy, capacity=capacity)
        packs, counts = _gather_matches(axis, packed, count)
        return packs, counts

    fn = jax.shard_map(shard_fn, mesh=mesh,
                       in_specs=(P(axis, None), P(), P()),
                       out_specs=(P(), P()),
                       check_vma=False)
    packs, counts = jax.jit(fn)(table, jnp.asarray(x, table.dtype),
                                jnp.asarray(y, table.dtype))
    return PushdownResult(packs, counts, counts.sum())


def pushdown_regex(mesh: Mesh, axis: str, capacity: int, dfa: DFA,
                   table: jnp.ndarray, str_lo: int,
                   str_hi: int) -> PushdownResult:
    """Distributed REGEXP_LIKE filter (paper §5.6) with the same economics."""

    def shard_fn(tbl):
        packed, count, _ = dfa_select(dfa, tbl, str_lo, str_hi,
                                      capacity=capacity)
        packs, counts = _gather_matches(axis, packed, count)
        return packs, counts

    fn = jax.shard_map(shard_fn, mesh=mesh, in_specs=(P(axis, None),),
                       out_specs=(P(), P()), check_vma=False)
    packs, counts = jax.jit(fn)(table)
    return PushdownResult(packs, counts, counts.sum())


class ShardedKVS(NamedTuple):
    """KVS sharded by bucket range: leading dim = shard (paper Fig. 4's
    parallel operators, each with its own DRAM controller)."""

    heads: jnp.ndarray    # [S, buckets_per_shard] int32 (local entry idx)
    keys: jnp.ndarray     # [S, cap] uint32
    values: jnp.ndarray   # [S, cap, v_width]
    nxt: jnp.ndarray      # [S, cap] int32
    n_buckets: int        # global bucket count


def build_sharded_kvs(keys: np.ndarray, values: np.ndarray,
                      n_buckets: int, n_shards: int) -> ShardedKVS:
    """Host-side build: bucket b lives on shard ``b % n_shards``."""
    keys = np.asarray(keys, np.uint32)
    values = np.asarray(values)
    # must match fib_hash exactly: the uint32 product WRAPS before >> 16.
    h = (((keys.astype(np.uint64) * 2654435769) & 0xFFFFFFFF) >> 16
         ).astype(np.uint32)
    b = (h % n_buckets).astype(np.int32)
    shard_of = b % n_shards
    bps = n_buckets // n_shards
    cap = 0
    per = [np.where(shard_of == s)[0] for s in range(n_shards)]
    cap = max(len(p) for p in per)
    cap = max(cap, 1)
    heads = np.full((n_shards, bps), -1, np.int32)
    k = np.zeros((n_shards, cap), np.uint32)
    v = np.zeros((n_shards, cap) + values.shape[1:], values.dtype)
    nxt = np.full((n_shards, cap), -1, np.int32)
    for s in range(n_shards):
        idx = per[s]
        for j, gi in enumerate(idx):
            local_b = b[gi] // n_shards
            nxt[s, j] = heads[s, local_b]
            heads[s, local_b] = j
            k[s, j] = keys[gi]
            v[s, j] = values[gi]
    return ShardedKVS(jnp.asarray(heads), jnp.asarray(k), jnp.asarray(v),
                      jnp.asarray(nxt), n_buckets)


def pushdown_lookup(mesh: Mesh, axis: str, kvs: ShardedKVS,
                    queries: jnp.ndarray, max_chain: int
                    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Distributed pointer-chase: queries are broadcast, each home shard
    walks the chains of the buckets it owns, answers combine by psum.

    Returns (values [q, v_width], found [q], steps [q] — per-query pointer
    hops, i.e. DRAM accesses, the Fig. 6 x-axis quantity).
    """
    n_shards = mesh.shape[axis]
    n_buckets = kvs.n_buckets

    def shard_fn(heads, keys, values, nxt, q):
        heads, keys, values, nxt = (heads[0], keys[0], values[0], nxt[0])
        sid = jax.lax.axis_index(axis)
        qb = fib_hash(q, n_buckets)
        mine = (qb % n_shards) == sid
        local_b = qb // n_shards
        ptr0 = jnp.where(mine, heads[local_b], -1)

        def body(carry, _):
            ptr, found_idx, steps = carry
            live = (ptr >= 0) & (found_idx < 0)
            safe = jnp.maximum(ptr, 0)
            hit = live & (keys[safe] == q)
            found_idx = jnp.where(hit, ptr, found_idx)
            steps = steps + live.astype(jnp.int32)
            ptr = jnp.where(live & ~hit, nxt[safe], ptr)
            return (ptr, found_idx, steps), None

        init = (ptr0, jnp.full_like(ptr0, -1), jnp.zeros_like(ptr0))
        (_, found_idx, steps), _ = jax.lax.scan(body, init, None,
                                                length=max_chain)
        found = found_idx >= 0
        vals = jnp.where(found[:, None], values[jnp.maximum(found_idx, 0)], 0)
        # exactly one shard answers each query -> sum combines.
        return (jax.lax.psum(vals, axis),
                jax.lax.psum(found.astype(jnp.int32), axis) > 0,
                jax.lax.psum(steps, axis))

    fn = jax.shard_map(shard_fn, mesh=mesh,
                       in_specs=(P(axis, None), P(axis, None),
                                 P(axis, None, None), P(axis, None), P()),
                       out_specs=(P(), P(), P()),
                       check_vma=False)
    return jax.jit(fn, static_argnums=())(kvs.heads, kvs.keys, kvs.values,
                                          kvs.nxt,
                                          queries.astype(jnp.uint32))


def bulk_transfer_bytes(table: jnp.ndarray) -> int:
    """Bytes the classical bulk-offload model would move (the baseline the
    paper's Fig. 5 compares against)."""
    return int(np.prod(table.shape)) * table.dtype.itemsize


def pushdown_bytes(result: PushdownResult, row_width: int,
                   itemsize: int) -> int:
    """Bytes actually moved by the pushdown collective (matches only)."""
    return int(result.moved_rows) * row_width * itemsize
