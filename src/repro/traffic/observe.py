"""In-scan observability plane for the streaming MN engine (paper §4.1).

The ECI paper's debugging toolkit captures EWF traces and checks NFA
protocol specs ONLINE, at the link's line rate, on the FPGA.  This module
is that toolkit for the production engine's fused ``lax.scan`` hot path —
everything below runs INSIDE the scan, on device, with no host sync:

* **EWF capture** — a bounded device-side ring of packed EWF v2 words
  (split into uint32 hi/lo pairs: the scan runs under JAX's default
  x64-disabled mode), fed from the step's five wire-event sites
  (``core.engine_mn.StepEvents``), overwrite-oldest, with per-line and
  per-msg-type filter masks.  Post-run the ring exports into the existing
  ``TraceBuffer``/JSON path (the step number rides in the txn field).

* **Online NFA checking** — ``core.tracing.compile_spec`` lowers each
  ``NFASpec`` to a dense powerset table; the per-line nondeterministic
  state SET is an int32 bitmask folded through the scan with ONE table
  gather per event site.  A violating transition resyncs the line and
  latches the first precise (step, line, symbol, states-before)
  counterexample, mirroring the host-side ``check_trace``.

* **Phase attribution** — per-transaction timestamps (window entry,
  engine acceptance, home park, fan-out replies, grant, retirement) fold
  into per-phase latency histograms: ``queue`` (issue window -> engine
  accept), ``service`` (accept -> retire), ``home`` (request parked ->
  grant issued) and ``fanout`` (park -> last invalidation reply), with
  p50/p99/p999 extraction and a Chrome/Perfetto trace-event export.

The plane is engineered for the <= 15% overhead budget ``bench_smoke``
gates (the engine step at R=64 is itself only a few dozen fused [R, L]
ops, so a naive implementation doubles the step):

* the ring append is ONE compacted write per step across all five sites:
  a single cumsum over the candidate lanes, a searchsorted INVERSION of
  it onto a fixed ``port``-wide window (the trace-port bandwidth, in
  words/step), and a ``port``-wide scatter — dense full-width scatters
  into the ring are ~20x slower on CPU XLA;
* each NFA site costs one gather: same-step symbol pairs (mixed
  ACK/DATA_DIRTY fan-out replies, the two downgrade flavours) use
  COMPOSITE table columns precompiled by ``_encoded_tables``, which also
  bakes resync-on-violation and the violating symbol into the entry
  (compile time verifies the pair commutes on every reachable state set,
  so any host-side interleaving of the pair agrees with the composite);
* the whole fold is gated behind one ``lax.cond`` on "any event this
  step", so the drain tail — typically ~half the step budget — pays one
  predicate AND.

Everything is OFF by default: ``run_stream(..., observe=None)`` traces
the exact program it always traced (bit-identical state, same jit cache
entry).  ``ObserveConfig`` is a hashable static config — it keys the
jitted streaming program alongside subset/width/home plan.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Dict, FrozenSet, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import transport as tp
from ..core.engine_mn import StepEvents
from ..core.messages import MsgType
from ..core.tracing import (N_SYMBOLS, SPECS, CompiledSpec, TraceBuffer,
                            compile_spec, symbol_id, symbol_id_name)
from .counters import LAT_EDGES, N_LAT_BUCKETS, bucket_counts

#: Attribution phase rows of ``phase_hist`` (shared LAT_EDGES buckets).
PHASES = ("queue", "service", "home", "fanout")
N_PHASES = len(PHASES)

#: Default online spec set: the two invariants every full-protocol stream
#: must satisfy.  (``readonly`` only holds on READ_ONLY-subset streams —
#: add it explicitly for those.)
DEFAULT_SPECS = ("req_resp", "single_writer")

#: Same-step symbol PAIRS that can hit one line together at one site and
#: therefore get composite table columns: mixed fan-out replies (the M/E
#: owner answers RESP_DATA_DIRTY while sharers answer RESP_ACK), the two
#: voluntary-downgrade flavours, the two home-downgrade flavours.
SYMBOL_PAIRS = (
    (symbol_id(int(MsgType.RESP_DATA_DIRTY), hresp=True),
     symbol_id(int(MsgType.RESP_ACK), hresp=True)),
    (symbol_id(int(MsgType.VOL_DOWNGRADE_S)),
     symbol_id(int(MsgType.VOL_DOWNGRADE_I))),
    (symbol_id(int(MsgType.HOME_DOWNGRADE_S)),
     symbol_id(int(MsgType.HOME_DOWNGRADE_I))),
)
N_COLS = N_SYMBOLS + len(SYMBOL_PAIRS)


class ObserveConfig(NamedTuple):
    """Static (hashable) observability switchboard — keys the jit cache.

    ``capture``/``capacity``: EWF ring on/off and its bound (words).
    ``specs``: names from ``core.tracing.SPECS`` to check online.
    ``attribution``: per-transaction phase histograms on/off.
    ``port``: trace-port bandwidth — max captured words per STEP (events
    beyond it in one step are dropped and counted, never silently).
    ``inject``: optional (step, line, msg_type) — a synthetic request
    word spliced into the request site at that step, for exercising the
    checker's counterexample path end-to-end (tests/CI only).
    """

    capture: bool = True
    capacity: int = 1 << 12
    specs: Tuple[str, ...] = DEFAULT_SPECS
    attribution: bool = True
    port: int = 256
    inject: Optional[Tuple[int, int, int]] = None


class ObsCarry(NamedTuple):
    """Scan-carried observability state (all device-resident; disabled
    features carry zero-size placeholders, costing nothing)."""

    ring_lo: jnp.ndarray     # [CAP] uint32 — EWF word bits [0:32)
    ring_hi: jnp.ndarray     # [CAP] uint32 — EWF word bits [32:64)
    ring_pos: jnp.ndarray    # [] int32 — words captured (total, unwrapped)
    ring_dropped: jnp.ndarray  # [] int32 — words lost to the port cap
    nfa_mask: jnp.ndarray    # [n_specs, L] int32 — per-line state bitmask
    viol_found: jnp.ndarray  # [n_specs] bool — counterexample latched
    viol_step: jnp.ndarray   # [n_specs] int32
    viol_line: jnp.ndarray   # [n_specs] int32
    viol_sym: jnp.ndarray    # [n_specs] int32 — online symbol id
    viol_mask: jnp.ndarray   # [n_specs] int32 — states before the event
    acc_step: jnp.ndarray    # [R, L] int32 — engine-accept step per txn
    park_step: jnp.ndarray   # [L] int32 — request-park step per line
    park_hd: jnp.ndarray     # [L] bool — parked txn fanned out
    last_reply: jnp.ndarray  # [L] int32 — newest fan-out reply arrival
    phase_hist: jnp.ndarray  # [N_PHASES, N_LAT_BUCKETS] int32


def compiled_specs(names: Tuple[str, ...]) -> Tuple[CompiledSpec, ...]:
    unknown = [n for n in names if n not in SPECS]
    assert not unknown, f"unknown specs {unknown}; have {sorted(SPECS)}"
    return tuple(compile_spec(SPECS[n]) for n in names)


def _reachable_masks(c: CompiledSpec) -> set:
    """State-set bitmasks reachable from start under resync semantics."""
    seen, frontier = {c.start_mask}, [c.start_mask]
    while frontier:
        m = frontier.pop()
        for s in range(N_SYMBOLS):
            nm = int(c.table[m, s]) or c.start_mask
            if nm not in seen:
                seen.add(nm)
                frontier.append(nm)
    return seen


def _encoded_tables(comp: Tuple[CompiledSpec, ...]
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Stack per-spec tables into the ENCODED online form.

    Entry layout (int32): bits [0:16) = next state-set mask with
    resync-on-violation already applied; bits [16:) = 1 + the violating
    symbol id, or 0 if the transition is clean.  One gather therefore
    yields the next mask AND the counterexample symbol.  Columns
    [0, N_SYMBOLS) are the single symbols; columns [N_SYMBOLS, N_COLS)
    are the ``SYMBOL_PAIRS`` composites (first symbol applied first);
    compile time asserts each pair COMMUTES on every reachable mask —
    final mask and violation verdict — so the composite agrees with any
    order the host-side checker replays the pair in."""
    if not comp:        # checking disabled: zero-spec tables, zero cost
        return (np.zeros((0, 1, N_COLS), np.int32),
                np.zeros((0,), np.int32))
    rows = max(c.table.shape[0] for c in comp)
    tab = np.zeros((len(comp), rows, N_COLS), np.int32)
    for i, c in enumerate(comp):
        n = c.table.shape[0]
        raw = c.table.astype(np.int64)                 # [n, N_SYMBOLS]
        sym = np.arange(N_SYMBOLS, dtype=np.int64)[None, :]
        tab[i, :n, :N_SYMBOLS] = np.where(
            raw == 0, c.start_mask | ((sym + 1) << 16), raw)

        def step1(m, s):
            """(next_mask_resynced, violated?) for one symbol on spec i."""
            nm = int(c.table[m, s])
            return (c.start_mask, True) if nm == 0 else (nm, False)

        reach = _reachable_masks(c)
        for pi, (a, b) in enumerate(SYMBOL_PAIRS):
            for m in range(n):
                m1, va = step1(m, a)
                m2, vb = step1(m1, b)
                first = a if va else b
                tab[i, m, N_SYMBOLS + pi] = m2 | (
                    ((first + 1) << 16) if (va or vb) else 0)
                if m in reach:
                    m1r, vb2 = step1(m, b)
                    m2r, va2 = step1(m1r, a)
                    if (m2r, va2 or vb2) != (m2, va or vb):
                        raise ValueError(
                            f"spec '{c.name}': symbol pair "
                            f"({symbol_id_name(a)}, {symbol_id_name(b)}) "
                            f"does not commute on state set "
                            f"{sorted(c.mask_states(m))} — the composite "
                            f"column cannot represent host-side "
                            f"interleavings")
    start = np.asarray([c.start_mask for c in comp], np.int32)
    return tab, start


def make_obs_carry(cfg: ObserveConfig, n_remotes: int, n_lines: int,
                   comp: Tuple[CompiledSpec, ...]) -> ObsCarry:
    R, L = n_remotes, n_lines
    cap = cfg.capacity if cfg.capture else 0
    n_specs = len(comp)
    z = jnp.zeros
    return ObsCarry(
        ring_lo=z((cap,), jnp.uint32),
        ring_hi=z((cap,), jnp.uint32),
        ring_pos=z((), jnp.int32),
        ring_dropped=z((), jnp.int32),
        nfa_mask=jnp.broadcast_to(
            jnp.asarray([c.start_mask for c in comp], jnp.int32)[:, None],
            (n_specs, L)).astype(jnp.int32),
        viol_found=z((n_specs,), bool),
        viol_step=z((n_specs,), jnp.int32),
        viol_line=z((n_specs,), jnp.int32),
        viol_sym=z((n_specs,), jnp.int32),
        viol_mask=z((n_specs,), jnp.int32),
        acc_step=z((R, L) if cfg.attribution else (0,), jnp.int32),
        park_step=z((L,) if cfg.attribution else (0,), jnp.int32),
        park_hd=z((L,) if cfg.attribution else (0,), bool),
        last_reply=z((L,) if cfg.attribution else (0,), jnp.int32),
        phase_hist=z((N_PHASES, N_LAT_BUCKETS) if cfg.attribution
                     else (0,), jnp.int32),
    )


# ---------------------------------------------------------------------------
# In-scan primitives (all traced).
# ---------------------------------------------------------------------------


def _pack32(msg, vc, pay, dirty, node, line, step):
    """EWF v2 word as a uint32 (lo, hi) pair — bit-compatible with
    ``core.messages.pack`` once recombined as ``hi << 32 | lo`` (the step
    number rides in the 16-bit txn field)."""
    u = lambda x: jnp.asarray(x).astype(jnp.uint32)
    lo = (u(msg) | (u(vc) << 4) | (u(pay) << 8) | (u(dirty) << 9)
          | (u(node) << 10) | ((u(line) & 0xFFFF) << 16))
    hi = (u(line) >> 16) | ((u(step) & 0xFFFF) << 16)
    return lo, hi


def _ring_append(oc: ObsCarry, keep, decode, t, cap: int, port: int
                 ) -> ObsCarry:
    """One compacted overwrite-oldest append of ALL kept lanes (in lane
    order) — a single cumsum, a searchsorted inversion onto the fixed
    ``port``-wide window, and one ``port``-wide scatter.  Lanes past the
    port bandwidth are dropped and counted.

    ``decode(lane)`` maps the selected global lane indices (a [port]
    vector) to the EWF word components (msg, vc, pay, dirty, node,
    line); only the ``port`` surviving lanes — not the full candidate
    width — pay the field gathers and the pack shift/or chain."""
    n = keep.shape[0]
    cum = jnp.cumsum(keep.astype(jnp.int32))
    total = cum[-1]
    j = jnp.arange(port, dtype=jnp.int32)
    lane = jnp.minimum(jnp.searchsorted(cum, j + 1, side="left"), n - 1)
    slot = jnp.where(j < total, (oc.ring_pos + j) % cap, cap)
    lo, hi = _pack32(*decode(lane), t)
    return oc._replace(
        ring_lo=oc.ring_lo.at[slot].set(lo, mode="drop"),
        ring_hi=oc.ring_hi.at[slot].set(hi, mode="drop"),
        ring_pos=oc.ring_pos + jnp.minimum(total, port),
        ring_dropped=oc.ring_dropped + jnp.maximum(total - port, 0))


def _hist_add(rows, masks, dts):
    """Fold stacked masked latency samples into histogram rows: ``masks``
    and ``dts`` are [k, ...]; returns rows + per-row bucket counts.
    (One-hot + reduce beats a scatter-add here: CPU XLA serializes
    scatter, while the [k, n, NB] bool reduction vectorizes.)"""
    add = bucket_counts(dts, masks, LAT_EDGES, axis=tuple(range(1, dts.ndim)))
    return rows + add.astype(jnp.int32)


class _Checker:
    """One step's worth of NFA folding over the encoded spec tables."""

    def __init__(self, table: jnp.ndarray, start: jnp.ndarray, t):
        self.table = table            # [n_specs, rows, N_COLS] encoded
        self.start = start            # [n_specs]
        self.t = t
        self.n_specs = table.shape[0]
        self.sidx = jnp.arange(self.n_specs)[:, None]

    def apply(self, oc: ObsCarry, present, col) -> ObsCarry:
        """Apply one event per line: ``present`` [L] bool, ``col`` a
        scalar or per-line [L] column id (single symbol or composite)."""
        if self.n_specs == 0:
            return oc
        L = oc.nfa_mask.shape[1]
        col = jnp.clip(jnp.asarray(col, jnp.int32), 0, N_COLS - 1)
        entry = self.table[self.sidx, oc.nfa_mask, col]  # [n_specs, L]
        nxt = entry & 0xFFFF
        vsym = (entry >> 16) - 1          # -1 = clean transition
        viol = present[None, :] & (vsym >= 0)
        mask2 = jnp.where(present[None, :], nxt, oc.nfa_mask)
        hit = viol.any(axis=1)
        new = hit & ~oc.viol_found
        vline = jnp.argmax(viol, axis=1).astype(jnp.int32)
        pick = lambda a: jnp.take_along_axis(a, vline[:, None],
                                             axis=1)[:, 0]
        return oc._replace(
            nfa_mask=mask2,
            viol_found=oc.viol_found | hit,
            viol_step=jnp.where(new, self.t, oc.viol_step),
            viol_line=jnp.where(new, vline, oc.viol_line),
            viol_sym=jnp.where(new, pick(vsym), oc.viol_sym),
            viol_mask=jnp.where(new, pick(oc.nfa_mask), oc.viol_mask))

    def pair_col(self, pa, pb, pair_idx: int):
        """Column + presence for a same-step symbol pair: the composite
        column when both fire on a line, the single symbol otherwise."""
        a, b = SYMBOL_PAIRS[pair_idx]
        col = jnp.where(pa & pb, N_SYMBOLS + pair_idx,
                        jnp.where(pa, a, b))
        return col, pa | pb


def fold_obs(cfg: ObserveConfig, table: jnp.ndarray, start: jnp.ndarray,
             oc: ObsCarry, ev: StepEvents, t, line_filt, type_filt,
             newly=None, born_d=None, retired=None) -> ObsCarry:
    """Fold one step's wire events into the observability carry (traced).

    Sites run in the engine's delivery order (hresp arrivals, voluntary
    downgrades, request acceptance, grant issue, home-downgrade delivery)
    — the same per-line serialization the host-side ``check_trace`` sees
    in the exported ring, so online and offline verdicts agree.
    ``newly``/``born_d``/``retired`` are the driver's ``[R, L]`` per-txn
    planes feeding phase attribution (ignored unless enabled).

    The entire fold sits behind one ``lax.cond`` on event presence: a
    step with no wire events, no acceptances and no retirements — the
    whole drain tail — costs a handful of reductions and a predicate.
    """
    R, L = ev.hresp_arr.shape
    lines = jnp.arange(L)
    with_attr = cfg.attribution and newly is not None
    inj_now = None
    if cfg.inject is not None:
        inj_now = (t == cfg.inject[0]) & (lines == cfg.inject[1])

    has_event = (ev.hresp_arr.any() | ev.vol_arr.any() | ev.req_acc.any()
                 | ev.grant.any() | ev.hd_arr.any())
    if with_attr:
        has_event = has_event | newly.any() | retired.any()
    if inj_now is not None:
        has_event = has_event | inj_now.any()

    def _fold(oc: ObsCarry) -> ObsCarry:
        chk = _Checker(table, start, t)
        segs = []       # (keep_flat, site field sources), lane-major

        def stage(keep, msg, klass, pay, dirty, node):
            """Record a capture site: ``keep`` is the full-width mask
            ([R, L] or [L]); the word fields stay UN-materialized (array
            sources or scalar constants; node=None means "the row index")
            — only the port-window lanes selected by ``_ring_append``
            ever gather/pack them."""
            if not cfg.capture:
                return
            if line_filt is not None:   # broadcasts over the last axis
                keep = keep & line_filt
            if type_filt is not None:
                keep = keep & (
                    type_filt[msg] if isinstance(msg, int)
                    else type_filt[jnp.clip(msg.astype(jnp.int32), 0, 15)])
            segs.append((keep.ravel(),
                         dict(shape=keep.shape, msg=msg, klass=klass,
                              pay=pay, dirty=dirty, node=node)))

        def decode(lane):
            """[port] global lane indices -> EWF word components."""
            z = jnp.zeros(lane.shape, jnp.int32)
            msg, pay, dirty, node, line, vc = z, z, z, z, z, z
            off = 0
            for keep_flat, info in segs:
                n = keep_flat.shape[0]
                in_site = (lane >= off) & (lane < off + n)
                idx = jnp.clip(lane - off, 0, n - 1)
                l = idx % L if len(info["shape"]) == 2 else idx

                def pick(cur, src):
                    if isinstance(src, int):
                        if src == 0:    # site regions are disjoint and
                            return cur  # cur starts 0 — nothing to do
                        return jnp.where(in_site, src, cur)
                    return jnp.where(
                        in_site, jnp.asarray(src, jnp.int32).ravel()[idx],
                        cur)

                msg = pick(msg, info["msg"])
                pay = pick(pay, info["pay"])
                dirty = pick(dirty, info["dirty"])
                node = (jnp.where(in_site, idx // L, node)
                        if info["node"] is None
                        else pick(node, info["node"]))
                line = jnp.where(in_site, l, line)
                vc = jnp.where(in_site, info["klass"] * 2 + (l & 1), vc)
                off += n
            return msg, vc, pay, dirty, node, line

        # ---- site 1: downgrade replies arrive at the home (hresp) -------
        stage(ev.hresp_arr, ev.hresp_msg, tp.CLASS_REMOTE_RESP,
              ev.hresp_dirty, ev.hresp_dirty, None)
        dd = int(MsgType.RESP_DATA_DIRTY)
        ack = int(MsgType.RESP_ACK)
        col, pres = chk.pair_col(
            (ev.hresp_arr & (ev.hresp_msg == dd)).any(0),
            (ev.hresp_arr & (ev.hresp_msg == ack)).any(0), 0)
        oc = chk.apply(oc, pres, col)
        if with_attr:
            oc = oc._replace(last_reply=jnp.where(
                ev.hresp_arr.any(0), t, oc.last_reply))

        # ---- site 2: voluntary downgrades absorbed at the home ----------
        stage(ev.vol_arr, ev.vol_msg, tp.CLASS_REMOTE_REQ,
              ev.vol_dirty, ev.vol_dirty, None)
        vs = int(MsgType.VOL_DOWNGRADE_S)
        vi = int(MsgType.VOL_DOWNGRADE_I)
        col, pres = chk.pair_col(
            (ev.vol_arr & (ev.vol_msg == vs)).any(0),
            (ev.vol_arr & (ev.vol_msg == vi)).any(0), 1)
        oc = chk.apply(oc, pres, col)

        # ---- site 3: request acceptance (one winner per line) -----------
        stage(ev.req_acc, ev.req_msg, tp.CLASS_REMOTE_REQ,
              0, 0, ev.req_node)
        oc = chk.apply(oc, ev.req_acc, ev.req_msg)
        if inj_now is not None:
            imsg = int(cfg.inject[2])
            stage(inj_now, imsg, tp.CLASS_REMOTE_REQ, 0, 0, 0)
            oc = chk.apply(oc, inj_now, imsg)
        if with_attr:
            oc = oc._replace(
                park_step=jnp.where(ev.req_acc, t, oc.park_step),
                park_hd=jnp.where(ev.req_acc, False, oc.park_hd))

        # ---- site 4: grant responses issued by the home -----------------
        gd = ev.grant_msg == dd
        stage(ev.grant, ev.grant_msg, tp.CLASS_HOME_RESP,
              ev.grant_pay, gd, ev.grant_node)
        oc = chk.apply(oc, ev.grant, ev.grant_msg)

        # ---- site 5: home-initiated downgrades delivered to remotes -----
        stage(ev.hd_arr, ev.hd_msg, tp.CLASS_HOME_REQ, 0, 0, None)
        hs = int(MsgType.HOME_DOWNGRADE_S)
        hi_ = int(MsgType.HOME_DOWNGRADE_I)
        col, pres = chk.pair_col(
            (ev.hd_arr & (ev.hd_msg == hs)).any(0),
            (ev.hd_arr & (ev.hd_msg == hi_)).any(0), 2)
        oc = chk.apply(oc, pres, col)
        if with_attr:
            oc = oc._replace(park_hd=oc.park_hd | ev.hd_arr.any(0))

        # ---- one compacted ring append for all sites --------------------
        if segs:
            oc = _ring_append(
                oc, jnp.concatenate([s[0] for s in segs]), decode,
                t, cfg.capacity, cfg.port)

        # ---- phase histograms: queue/service per txn, home/fanout per
        # ---- line — one stacked bucket-add each ------------------------
        if with_attr:
            hist = _hist_add(
                oc.phase_hist[0:2],
                jnp.stack([newly, retired]),
                jnp.stack([t - born_d, t - oc.acc_step]))
            hist2 = _hist_add(
                oc.phase_hist[2:4],
                jnp.stack([ev.grant, ev.grant & oc.park_hd]),
                jnp.stack([t - oc.park_step,
                           oc.last_reply - oc.park_step]))
            oc = oc._replace(
                phase_hist=jnp.concatenate([hist, hist2]),
                acc_step=jnp.where(newly, t, oc.acc_step))
        return oc

    return jax.lax.cond(has_event, _fold, lambda oc: oc, oc)


# ---------------------------------------------------------------------------
# Host-side readout.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class OnlineViolation:
    """First counterexample one online spec latched during the scan."""

    spec: str
    step: int
    line: int
    symbol: str
    states_before: FrozenSet[str]

    def __str__(self) -> str:
        return (f"[{self.spec}] step {self.step} line {self.line}: "
                f"'{self.symbol}' not allowed from "
                f"{set(self.states_before)}")


@dataclasses.dataclass
class ObsResult:
    """Host-side digest of an observed run."""

    config: ObserveConfig
    words: np.ndarray               # [n_kept] uint64, oldest first
    captured_total: int             # words seen (>= len(words) on wrap)
    dropped: int                    # words lost to the port cap
    violations: List[OnlineViolation]
    phase_hist: Optional[np.ndarray]   # [N_PHASES, N_LAT_BUCKETS]

    def trace_buffer(self) -> TraceBuffer:
        return TraceBuffer.from_words(
            self.words, capacity=max(self.config.capacity, 1))

    def phase_percentiles(self) -> Dict[str, Dict[str, float]]:
        from .counters import hist_percentiles
        if self.phase_hist is None:
            return {}
        return {ph: hist_percentiles(self.phase_hist[i])
                for i, ph in enumerate(PHASES)}

    def metrics(self) -> Dict[str, object]:
        return {
            "captured_words": int(len(self.words)),
            "captured_total": int(self.captured_total),
            "dropped_words": int(self.dropped),
            "specs": list(self.config.specs),
            "violations": [dataclasses.asdict(v) |
                           {"states_before": sorted(v.states_before)}
                           for v in self.violations],
            "phase_hist": (self.phase_hist.tolist()
                           if self.phase_hist is not None else None),
            "phase_percentiles": self.phase_percentiles(),
        }


def finalize_obs(cfg: ObserveConfig, oc: ObsCarry,
                 comp: Tuple[CompiledSpec, ...]) -> ObsResult:
    pos = int(oc.ring_pos)
    words = np.zeros((0,), np.uint64)
    if cfg.capture and pos:
        lo = np.asarray(oc.ring_lo, np.uint64)
        hi = np.asarray(oc.ring_hi, np.uint64)
        full = (hi << np.uint64(32)) | lo
        if pos <= cfg.capacity:
            words = full[:pos]
        else:                       # wrapped: rotate oldest-first
            start = pos % cfg.capacity
            words = np.concatenate([full[start:], full[:start]])
    violations = []
    found = np.asarray(oc.viol_found)
    for i, c in enumerate(comp):
        if bool(found[i]):
            violations.append(OnlineViolation(
                spec=c.name,
                step=int(oc.viol_step[i]),
                line=int(oc.viol_line[i]),
                symbol=symbol_id_name(int(oc.viol_sym[i])),
                states_before=c.mask_states(int(oc.viol_mask[i]))))
    hist = (np.asarray(oc.phase_hist) if cfg.attribution else None)
    return ObsResult(config=cfg, words=words, captured_total=pos,
                     dropped=int(oc.ring_dropped),
                     violations=violations, phase_hist=hist)


# ---------------------------------------------------------------------------
# Perfetto / Chrome trace-event timeline export.
# ---------------------------------------------------------------------------


def perfetto_events(tb: TraceBuffer, n_homes: int = 1) -> Dict[str, object]:
    """Chrome trace-event JSON from a captured EWF trace.

    One engine step maps to one microsecond of trace time.  Tracks:
    ``home h`` processes carry the per-home wire activity (requests
    accepted, grants issued, voluntary downgrades and fan-out replies
    absorbed) plus per-line transaction SPANS (request park -> grant);
    ``remote r`` processes carry home-initiated downgrade deliveries.
    Load the result into https://ui.perfetto.dev or chrome://tracing.
    """
    events: List[dict] = []
    open_req: Dict[int, Tuple[int, str]] = {}     # line -> (step, name)
    for m in tb.messages():
        msg, vc = int(m.msg_type), int(m.vc)
        node, line, step = int(m.node), int(m.line), int(m.txn)
        name = MsgType(msg).name
        klass = vc // 2
        if klass == tp.CLASS_HOME_REQ:
            pid, label = f"remote {node}", "deliver"
        else:
            pid = f"home {line % max(n_homes, 1)}"
            label = {tp.CLASS_REMOTE_REQ: "accept",
                     tp.CLASS_HOME_RESP: "grant",
                     tp.CLASS_REMOTE_RESP: "reply"}.get(klass, "wire")
        events.append({
            "name": f"{name} L{line}", "ph": "i", "ts": step, "s": "t",
            "pid": pid, "tid": f"{label}",
            "args": {"line": line, "node": node, "vc": vc,
                     "dirty": bool(m.dirty)},
        })
        if klass == tp.CLASS_REMOTE_REQ and msg in (
                int(MsgType.REQ_READ_SHARED), int(MsgType.REQ_READ_EXCL),
                int(MsgType.REQ_UPGRADE)):
            open_req[line] = (step, name)
        elif klass == tp.CLASS_HOME_RESP and line in open_req:
            t0, rname = open_req.pop(line)
            events.append({
                "name": f"{rname} L{line}", "ph": "X",
                "ts": t0, "dur": max(step - t0, 1),
                "pid": f"home {line % max(n_homes, 1)}",
                "tid": f"line {line}",
                "args": {"line": line, "grant": name,
                         "latency_steps": step - t0},
            })
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": {"time_unit": "1 us == 1 engine step"}}


def write_perfetto(tb: TraceBuffer, path: str, n_homes: int = 1) -> None:
    with open(path, "w") as f:
        json.dump(perfetto_events(tb, n_homes=n_homes), f)
