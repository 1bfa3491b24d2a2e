"""Hardware-style perf counters for the streaming traffic subsystem.

Real coherence fabrics expose exactly this telemetry: per-message-type
delivery counts, invalidation fan-out, per-initiator retirement-latency
histograms, channel occupancy and a starvation bound (max request wait).
Here the counters are a small NamedTuple of dense arrays folded through
the driver's ``lax.scan`` carry — updated entirely on-device, read out
once at the end of a run.

The per-message-type counts live in the engine state itself
(``msg_count``, extended by the driver into a per-run delta); everything
else accumulates in ``Counters``.

**Validation** (``replay_reference`` + ``assert_counts_match``): the
driver's retirement trace is a per-line linearization of the streamed
execution, so replaying it op-by-op into the atomic ``MultiNodeRef``
oracle must reproduce the engine's message counts EXACTLY — modulo one
documented identity: an upgrade that lost a race costs the engine one
extra ``REQ_UPGRADE`` + ``RESP_NACK`` pair before it retires as the
``REQ_READ_EXCL`` the oracle sees.  For eviction-free LOAD/STORE streams
(all of ``traffic.workloads``) there are no other divergences; voluntary
downgrades crossing home-initiated recalls would break the per-line
serialization the replay relies on, which is why the generators never
emit EVICT.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..core.messages import MsgType
from ..core.multinode import MultiNodeRef
from ..core.protocol import LocalOp

#: retirement-latency histogram bucket edges (engine steps); bucket i
#: holds lat in [edge[i-1], edge[i]), the last bucket is the overflow.
LAT_EDGES = np.asarray([1, 2, 4, 8, 16, 32, 64, 128, 256], np.int32)
N_LAT_BUCKETS = len(LAT_EDGES) + 1

#: sojourn (arrival -> retirement) histogram edges for OPEN-LOOP runs.
#: Sojourn includes queue wait, which under overload grows with the run
#: length rather than the protocol depth, so the range extends far past
#: LAT_EDGES — a p99 in the 8192 overflow bucket is the knee curve's
#: "past saturation" signal.
SOJOURN_EDGES = np.asarray([1 << i for i in range(14)], np.int32)
N_SOJ_BUCKETS = len(SOJOURN_EDGES) + 1

#: the four coherence channel classes, in Counters.occ_* order.
CHANNELS = ("req", "resp", "hreq", "hresp")

#: Occupancy accumulators fold up to R*L (65,536 at R=64/L=1024) per step,
#: so a single int32 wraps after ~2^31 / 2^16 = 32,768 steps — BELOW the
#: default step budget of a full R=64 stream (``default_steps(256, 64)`` =
#: 35,904).  JAX's default x64-disabled mode silently downcasts an int64
#: carry back to int32, so the fix is a hi/lo int32 PAIR: ``lo`` keeps the
#: low ACC_SHIFT bits, every update moves the overflow bits into ``hi``.
#: Exact up to 2^(31 + ACC_SHIFT) = 2^61 — per-step deltas must stay below
#: 2^31 - 2^ACC_SHIFT, comfortably above any [R, L] slab this repo runs.
ACC_SHIFT = 30
ACC_MASK = (1 << ACC_SHIFT) - 1


def acc_add(hi: jnp.ndarray, lo: jnp.ndarray, delta: jnp.ndarray
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One exact hi/lo accumulator update (traced; int32 in, int32 out)."""
    raw = lo + delta                     # < 2^ACC_SHIFT + 2^31-2^ACC_SHIFT
    return hi + (raw >> ACC_SHIFT), raw & ACC_MASK


def acc_total(hi, lo) -> np.ndarray:
    """Host-side readout of a hi/lo pair as exact int64."""
    return (np.asarray(hi, np.int64) << ACC_SHIFT) + np.asarray(lo, np.int64)


class Counters(NamedTuple):
    """Scan-carried telemetry (all int32, device-resident).

    The per-step-summed accumulators (``occ_sum_*``, ``mshr_sum_*``) are
    hi/lo int32 pairs — see ``acc_add``; read them out with ``acc_total``.
    """

    lat_hist: jnp.ndarray   # [R, N_LAT_BUCKETS] retirement latency histo
    max_wait: jnp.ndarray   # [R] worst request wait observed (starvation)
    retired: jnp.ndarray    # [R] ops retired
    occ_sum_hi: jnp.ndarray  # [4] per-class channel occupancy, summed/step
    occ_sum_lo: jnp.ndarray  # [4] (hi/lo int32 pair, exact to 2^61)
    occ_peak: jnp.ndarray   # [4] per-class peak occupancy
    mshr_sum_hi: jnp.ndarray  # [] in-flight transactions, summed/step
    mshr_sum_lo: jnp.ndarray  # [] (hi/lo int32 pair)
    mshr_peak: jnp.ndarray  # [] peak in-flight transactions
    steps: jnp.ndarray      # [] steps folded (the full scan budget)
    active_steps: jnp.ndarray  # [] steps with traffic in flight — the
    #                            denominator for sustained rates (the
    #                            post-drain idle tail must not dilute them)


def make_counters(n_remotes: int) -> Counters:
    return Counters(
        lat_hist=jnp.zeros((n_remotes, N_LAT_BUCKETS), jnp.int32),
        max_wait=jnp.zeros((n_remotes,), jnp.int32),
        retired=jnp.zeros((n_remotes,), jnp.int32),
        occ_sum_hi=jnp.zeros((4,), jnp.int32),
        occ_sum_lo=jnp.zeros((4,), jnp.int32),
        occ_peak=jnp.zeros((4,), jnp.int32),
        mshr_sum_hi=jnp.zeros((), jnp.int32),
        mshr_sum_lo=jnp.zeros((), jnp.int32),
        mshr_peak=jnp.zeros((), jnp.int32),
        steps=jnp.zeros((), jnp.int32),
        active_steps=jnp.zeros((), jnp.int32),
    )


def bucket_counts(x: jnp.ndarray, mask: jnp.ndarray, edges: np.ndarray,
                  axis) -> jnp.ndarray:
    """Histogram of the integer samples ``x`` where ``mask`` holds, over
    the sorted ``edges`` (bucket i holds x in [edge[i-1], edge[i]), the
    last bucket is the overflow), summed over ``axis``; the bucket axis
    is last (traced).

    The bucket is ``searchsorted(edges, x, side="right")`` taken as
    compares: no per-element gather on a TPU.  XLA fuses the one-hot
    compare into the reduction, so no [..., n_buckets] plane is
    materialised."""
    edges = jnp.asarray(edges)
    bucket = (x[..., None] >= edges).sum(-1)
    onehot = bucket[..., None] == jnp.arange(edges.shape[0] + 1)
    return (onehot & mask[..., None]).sum(axis=axis)


def update_counters(ctr: Counters, st, *, retired: jnp.ndarray,
                    lat: jnp.ndarray, outstanding: jnp.ndarray,
                    head_wait: jnp.ndarray,
                    step_active: jnp.ndarray,
                    backend: str = "xla") -> Counters:
    """Fold one engine step's events into the counters (traced).

    Args:
      st: the post-step ``EngineMNState`` (for channel occupancy).
      retired: [R, L] ops that retired this step.
      lat: [R, L] their first-attempt-to-retirement latency in steps
        (valid under ``retired``; also the current wait of in-flight ops).
      outstanding: [R, L] transactions still in flight after this step.
      head_wait: [R] wait of each remote's not-yet-accepted head op.
      step_active: [] bool — stream unconsumed or engine non-quiescent.
      backend: "pallas" routes the latency-histogram fold through the
        ``kernels.coherency_step.lat_hist`` kernel (bit-identical).
    """
    if backend == "pallas":
        from ..kernels import ops as _kops
        hist = ctr.lat_hist + _kops.lat_hist(
            lat, retired, tuple(int(e) for e in LAT_EDGES))
    else:
        hist = ctr.lat_hist + bucket_counts(lat, retired, LAT_EDGES, axis=1)

    # the starvation bound: worst of (retired latency, in-flight wait,
    # head-of-stream wait) — a starved request never retires, so the live
    # waits matter as much as the completed ones.
    live = jnp.where(retired | outstanding, lat, 0).max(axis=1)
    max_wait = jnp.maximum(ctr.max_wait, jnp.maximum(live, head_wait))

    occ = jnp.stack([(ch.msg != int(MsgType.NOP)).sum()
                     for ch in (st.ch_req, st.ch_resp, st.ch_hreq,
                                st.ch_hresp)]).astype(jnp.int32)
    # MSHR occupancy: transactions in flight across all remotes — the
    # x-axis of the issue-width occupancy/throughput curve.
    mshr = outstanding.sum().astype(jnp.int32)
    occ_hi, occ_lo = acc_add(ctr.occ_sum_hi, ctr.occ_sum_lo, occ)
    mshr_hi, mshr_lo = acc_add(ctr.mshr_sum_hi, ctr.mshr_sum_lo, mshr)
    return Counters(
        lat_hist=hist,
        max_wait=max_wait,
        retired=ctr.retired + retired.sum(axis=1).astype(jnp.int32),
        occ_sum_hi=occ_hi,
        occ_sum_lo=occ_lo,
        occ_peak=jnp.maximum(ctr.occ_peak, occ),
        mshr_sum_hi=mshr_hi,
        mshr_sum_lo=mshr_lo,
        mshr_peak=jnp.maximum(ctr.mshr_peak, mshr),
        steps=ctr.steps + 1,
        active_steps=ctr.active_steps + step_active.astype(jnp.int32),
    )


def hist_percentiles(hist: np.ndarray,
                     edges: np.ndarray = LAT_EDGES,
                     qs: Tuple[float, ...] = (0.5, 0.99, 0.999)
                     ) -> Dict[str, float]:
    """Percentiles from a bucketed latency histogram (host-side).

    Returns the UPPER edge of the bucket containing each quantile — the
    conservative bound a bucketed histogram can actually certify (the
    true latency is strictly below it; bucket i spans [edge[i-1],
    edge[i])).  A quantile landing in the overflow bucket reports
    ``inf``: the histogram only knows the latency was >= the last edge.
    Empty histograms report 0 for every quantile.  Keys are "p50"-style
    ("0.999" -> "p999")."""
    counts = np.asarray(hist, np.float64)
    uppers = np.concatenate([np.asarray(edges, np.float64), [np.inf]])
    assert counts.shape == uppers.shape, (counts.shape, len(edges))
    total = counts.sum()
    out = {}
    cdf = np.cumsum(counts)
    for q in qs:
        key = "p" + format(q * 100, "g").replace(".", "")
        if total == 0:
            out[key] = 0.0
            continue
        idx = int(np.searchsorted(cdf, q * total, side="left"))
        out[key] = float(uppers[min(idx, len(uppers) - 1)])
    return out


def summarize(ctr: Counters, msg_count: np.ndarray,
              payload_msgs: int = 0) -> Dict[str, object]:
    """Host-side digest of a run: the numbers a benchmark row reports.

    Sustained rates divide by ``active_steps`` (steps with traffic in
    flight), NOT the scan budget — a generous post-drain idle tail must
    not dilute throughput or occupancy."""
    steps = max(int(ctr.steps), 1)
    active = max(int(ctr.active_steps), 1)
    retired = np.asarray(ctr.retired)
    mc = np.asarray(msg_count, np.int64)
    # fan-out is per exclusive GRANT: NACKed upgrade attempts are counted
    # as requests but fan out nothing, so subtract them.
    nacks = int(mc[int(MsgType.RESP_NACK)])
    excl = int(mc[int(MsgType.REQ_READ_EXCL)]
               + mc[int(MsgType.REQ_UPGRADE)]) - nacks
    inval = int(mc[int(MsgType.HOME_DOWNGRADE_I)])
    return {
        "steps": steps,
        "active_steps": active,
        "ops_retired": int(retired.sum()),
        "ops_per_step": retired.sum() / active,
        # interconnect cost per retired op — the protocol-subset figure of
        # merit (bench_subsets compares it across the §3.4 lattice).
        "msgs_per_op": float(mc.sum()) / max(int(retired.sum()), 1),
        "retired_per_remote": retired.tolist(),
        "max_wait": np.asarray(ctr.max_wait).tolist(),
        "lat_hist": np.asarray(ctr.lat_hist).tolist(),
        # tail latency (ROADMAP open-loop item): aggregate + per-remote
        # p50/p99/p999 pulled from the bucketed histograms — upper bucket
        # edges, inf when the quantile lands in the overflow bucket.
        "latency_percentiles":
            hist_percentiles(np.asarray(ctr.lat_hist).sum(axis=0)),
        "latency_percentiles_per_remote": [
            hist_percentiles(row) for row in np.asarray(ctr.lat_hist)],
        "invalidations": inval,
        "inval_per_excl_grant": inval / max(excl, 1),
        "nacks": nacks,
        "mean_occupancy": {
            ch: float(acc_total(ctr.occ_sum_hi, ctr.occ_sum_lo)[i]) / active
            for i, ch in enumerate(CHANNELS)},
        "peak_occupancy": {
            ch: int(np.asarray(ctr.occ_peak)[i])
            for i, ch in enumerate(CHANNELS)},
        "mean_mshr_occupancy":
            float(acc_total(ctr.mshr_sum_hi, ctr.mshr_sum_lo)) / active,
        "peak_mshr_occupancy": int(ctr.mshr_peak),
        "payload_msgs": int(payload_msgs),
        "messages": {MsgType(i).name: int(mc[i]) for i in range(16)
                     if mc[i]},
    }


def sojourn_summary(run) -> Dict[str, object]:
    """Host-side digest of an OPEN-LOOP run's serving metrics.

    Sojourn is arrival -> retirement (queue wait + service); admit wait is
    arrival -> admission (the queueing component alone).  Percentiles are
    the same conservative upper-bucket-edge bounds as
    ``hist_percentiles`` — ``inf`` means the quantile fell past the last
    ``SOJOURN_EDGES`` edge, i.e. the system was past saturation.
    ``backlog`` is the number of arrived-but-never-issued ops left when
    the step budget ran out: > 0 is the unserved-queue-growth signature
    of overload."""
    assert run.sojourn_hist is not None, \
        "sojourn_summary needs an open-loop StreamRun (cfg.arrivals set)"
    return {
        "sojourn_percentiles":
            hist_percentiles(run.sojourn_hist, SOJOURN_EDGES),
        "admit_wait_percentiles":
            hist_percentiles(run.admit_wait_hist, SOJOURN_EDGES),
        "sojourn_hist": np.asarray(run.sojourn_hist).tolist(),
        "admit_wait_hist": np.asarray(run.admit_wait_hist).tolist(),
        "backlog": int(run.backlog),
        "completed": bool(run.completed),
    }


# ---------------------------------------------------------------------------
# Oracle replay: the counter-validation path.
# ---------------------------------------------------------------------------


class RetirementTrace(NamedTuple):
    """Compact retirement linearization of a streamed run.

    One int32 per workload slot — ``retire_step[t, r]`` is the engine step
    at which remote ``r``'s ``t``-th stream op retired (-1 = never
    retired).  Op/line/value ride along straight from the workload arrays,
    so the whole record is O(T * R): the earlier dense per-step encoding
    (three ``[S, R, L]`` slabs) hit ~14 GB at R=64/L=1024 with the default
    step budget, five orders of magnitude more than the retirements it
    described.
    """

    retire_step: np.ndarray  # [T, R] int32, -1 = never retired
    op: np.ndarray           # [T, R] int8  LocalOp (from the workload)
    line: np.ndarray         # [T, R] int32 (from the workload)
    value: np.ndarray        # [T, R]       (from the workload)
    n_lines: int             # oracle sizing (lines no op touched still
    #                          need directory slots)


def replay_reference(trace: RetirementTrace, moesi: bool = True,
                     subset=None, n_homes: int = 1
                     ) -> Tuple[MultiNodeRef, np.ndarray]:
    """Replay a streaming run's retirement linearization atomically.

    Retired slots replay in (retire_step, remote, program-order) order:
    per line the engine serializes transactions, so retirement order IS a
    legal atomic order; same-step retirements on one line can only be
    reads (an exclusive grant excludes concurrent sharers), which commute
    — any tie-break within a step is equivalent.  Returns the oracle and
    its per-message-type counts [16].  ``subset`` puts the oracle in its
    subset-aware mode (the replay then also PROVES the retired stream
    respected the workload guarantee — an out-of-subset op raises);
    ``n_homes`` replays into the multi-home oracle, whose lockstep shard
    mirror extends counter validation into a sharding-invariance proof.
    """
    rs = np.asarray(trace.retire_step)
    ops = np.asarray(trace.op)
    lines = np.asarray(trace.line)
    vals = np.asarray(trace.value)
    ref = MultiNodeRef(trace.n_lines, n_remotes=rs.shape[1], moesi=moesi,
                       subset=subset, n_homes=n_homes)
    # one vectorized pass replaces the old per-step nonzero scan: gather
    # the retired slots, order them by (step, remote, t).
    tt, rr = np.nonzero(rs >= 0)
    order = np.lexsort((tt, rr, rs[tt, rr]))
    for t, r in zip(tt[order], rr[order]):
        op = int(ops[t, r])
        if op == int(LocalOp.LOAD):
            ref.load(int(r), int(lines[t, r]))
        elif op == int(LocalOp.STORE):
            ref.store(int(r), int(lines[t, r]), float(vals[t, r]))
        elif op == int(LocalOp.EVICT):
            ref.evict(int(r), int(lines[t, r]))
    counts = np.zeros(16, np.int64)
    for name, _, _ in ref.trace:
        counts[int(MsgType[name])] += 1
    return ref, counts


def assert_counts_match(msg_count: np.ndarray, ref_counts: np.ndarray
                        ) -> None:
    """Engine counters must equal the oracle's EXACTLY, after the one
    legal divergence: each upgrade race costs the engine one extra
    ``REQ_UPGRADE`` + ``RESP_NACK`` before the retry the oracle sees."""
    eng = np.asarray(msg_count, np.int64)
    nacks = int(eng[int(MsgType.RESP_NACK)])
    expect = np.asarray(ref_counts, np.int64).copy()
    expect[int(MsgType.REQ_UPGRADE)] += nacks
    expect[int(MsgType.RESP_NACK)] += nacks
    mism = np.nonzero(eng != expect)[0]
    assert mism.size == 0, (
        "engine/oracle message-count mismatch: " + ", ".join(
            f"{MsgType(i).name}: engine={eng[i]} oracle={expect[i]}"
            for i in mism))


def validate_run(run, moesi: bool = True, subset=None,
                 n_homes: int = 1) -> MultiNodeRef:
    """Full validation of a traced ``StreamRun``: the run completed, and
    its counters match the atomic oracle at quiescence.  Returns the
    replayed oracle (callers can go on to compare final states).
    ``subset`` validates against the subset-aware oracle — the per-
    lattice-member acceptance path of the protocol-parametric engine;
    ``n_homes`` matches the engine's home count (the multi-home oracle's
    shard mirror then certifies the interleaving too)."""
    assert run.completed, "stream did not drain within the step budget"
    assert run.trace is not None, "run_stream(collect_trace=True) required"
    ref, counts = replay_reference(run.trace, moesi, subset=subset,
                                   n_homes=n_homes)
    ref.check_all()
    assert_counts_match(run.msg_count, counts)
    return ref
