"""Coherency-step Pallas kernels: BIT-exact agreement with the engine's
XLA expressions (``kernels/ref.py`` holds those expressions verbatim),
plus whole-engine pallas-vs-xla bisimulation on seeded schedules.

These are integer kernels, so every comparison is assert_array_equal —
never allclose.  On CPU the kernels execute in interpret mode (the CI
path); on TPU the same tests exercise the real Mosaic lowering.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine_mn import (EngineMN, KERNEL_BACKENDS,
                                  resolve_kernel_backend)
from repro.core.protocol import LocalOp
from repro.kernels import coherency_step as coh
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.traffic import (EngineConfig, StreamConfig, WorkloadSpec,
                           run_stream, validate_run)
from repro.traffic.counters import LAT_EDGES

SEED = 1234


# ---------------------------------------------------------------------------
# Per-kernel bit-exactness on random planes.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(16,), (8, 16), (4, 8, 16), (3, 33),
                                   (64, 128)])
def test_credit_rank_bit_exact(shape):
    rng = np.random.default_rng(SEED)
    active = jnp.asarray(rng.random(shape) < 0.4)
    cand = jnp.asarray((rng.random(shape) < 0.3)) & ~active
    got = coh.credit_rank(active, cand, interpret=True)
    want = kref.credit_rank_ref(active, cand)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert got.dtype == want.dtype


@pytest.mark.parametrize("P,L,lead", [(3, 16, ()), (9, 16, ()),
                                      (65, 32, ()), (5, 8, (4,))])
def test_arb_winner_bit_exact(P, L, lead):
    rng = np.random.default_rng(SEED + P)
    ready = jnp.asarray(rng.random(lead + (P, L)) < 0.3)
    arb = jnp.asarray(rng.integers(0, P, lead + (L,)).astype(np.int32))
    got = coh.arb_winner(ready, arb, interpret=True)
    want = kref.arb_winner_ref(ready, arb)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("shape", [(8, 16), (4, 8, 16), (5, 7)])
def test_count_fold_bit_exact(shape):
    rng = np.random.default_rng(SEED)
    mask = jnp.asarray(rng.random(shape) < 0.5)
    msg = jnp.asarray(rng.integers(0, 16, shape).astype(np.int8))
    pay = jnp.asarray(rng.random(shape) < 0.5)
    gc, gp = coh.count_fold(mask, msg, pay, interpret=True)
    wc, wp = kref.count_fold_ref(mask, msg, pay)
    np.testing.assert_array_equal(np.asarray(gc), np.asarray(wc))
    assert int(gp) == int(wp)


@pytest.mark.parametrize("R,L", [(4, 16), (8, 32), (3, 7)])
def test_lat_hist_bit_exact(R, L):
    rng = np.random.default_rng(SEED)
    # include negative latencies (an un-born in-flight lane) and values
    # straddling every bucket edge.
    lat = jnp.asarray(rng.integers(-4, 600, (R, L)).astype(np.int32))
    retired = jnp.asarray(rng.random((R, L)) < 0.5)
    edges = tuple(int(e) for e in LAT_EDGES)
    got = coh.lat_hist(lat, retired, edges, interpret=True)
    want = kref.lat_hist_ref(lat, retired, edges)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# Seeded-schedule bisimulation: the full engine under kernel_backend=
# "pallas" must match the default XLA engine bit-for-bit, state and all.
# ---------------------------------------------------------------------------


def _drive(backend, moesi):
    L, B, R = 16, 2, 6
    rng = np.random.default_rng(SEED)
    backing = jnp.asarray(rng.normal(size=(L, B)).astype(np.float32))
    eng = EngineMN(backing, n_remotes=R, moesi=moesi,
                   kernel_backend=backend)
    st = eng.init()
    for t in range(30):
        op = np.zeros((R, L), np.int8)
        for r in range(R):
            op[r, rng.integers(0, L)] = rng.choice(
                [int(LocalOp.LOAD), int(LocalOp.STORE)])
        st, _ = eng.step(st, jnp.asarray(op),
                         jnp.full((R, L, B), float(t), jnp.float32))
    return eng.drain(st, 256)


@pytest.mark.parametrize("moesi", [True, False])
def test_engine_pallas_vs_xla_bit_identical(moesi):
    st_x = _drive("xla", moesi)
    st_p = _drive("pallas", moesi)
    for path, (x, p) in zip(
            jax.tree_util.tree_leaves_with_path(st_x),
            zip(jax.tree_util.tree_leaves(st_x),
                jax.tree_util.tree_leaves(st_p))):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(p),
                                      err_msg=str(path[0]))


def test_stream_pallas_vs_xla_bit_identical():
    """The full streaming pipeline (driver scan + counters) under the
    pallas backend — counters, message counts and the retirement trace
    all bit-identical, and the oracle replay still validates."""
    cfg = StreamConfig(workload=WorkloadSpec("zipfian", ops=24, seed=7),
                       width=2, collect_trace=True)
    a = run_stream(EngineConfig(remotes=6, lines=16).build(), cfg)
    b = run_stream(EngineConfig(remotes=6, lines=16,
                                kernel_backend="pallas").build(), cfg)
    assert a.completed and b.completed
    np.testing.assert_array_equal(a.msg_count, b.msg_count)
    assert a.payload_msgs == b.payload_msgs
    np.testing.assert_array_equal(a.trace.retire_step, b.trace.retire_step)
    for f, (x, y) in zip(a.counters._fields, zip(a.counters, b.counters)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f)
    validate_run(b)


# ---------------------------------------------------------------------------
# Backend selection plumbing.
# ---------------------------------------------------------------------------


def test_backend_resolution_and_validation():
    assert KERNEL_BACKENDS == ("xla", "pallas")
    assert resolve_kernel_backend("") == "xla"
    assert resolve_kernel_backend("pallas") == "pallas"
    with pytest.raises(ValueError, match="kernel_backend"):
        resolve_kernel_backend("cuda")
    with pytest.raises(ValueError, match="kernel_backend"):
        EngineConfig(kernel_backend="cuda")
    old = os.environ.get("REPRO_KERNEL_BACKEND")
    try:
        os.environ["REPRO_KERNEL_BACKEND"] = "pallas"
        assert resolve_kernel_backend("") == "pallas"
        # an explicit argument wins over the environment
        assert resolve_kernel_backend("xla") == "xla"
        eng = EngineMN(jnp.zeros((8, 2), jnp.float32), n_remotes=2)
        assert eng.kernel_backend == "pallas"
    finally:
        if old is None:
            os.environ.pop("REPRO_KERNEL_BACKEND", None)
        else:
            os.environ["REPRO_KERNEL_BACKEND"] = old


def test_default_backend_is_xla_and_shares_cache():
    """The default engine must keep compiling the EXACT pre-kernel
    program: same lru-cache entry for the 4-arg historical call and the
    explicit-backend call."""
    from repro.core.engine_mn import _jitted_step_mn
    eng = EngineMN(jnp.zeros((8, 2), jnp.float32), n_remotes=2)
    assert eng.kernel_backend == "xla"
    assert _jitted_step_mn(eng.subset.name, False, 1, 0) is eng._step
    assert _jitted_step_mn(eng.subset.name, False, 1, 0, "xla") \
        is eng._step


# ---------------------------------------------------------------------------
# Packed directory planes: word-level helpers, the two packed kernels,
# and full packed-vs-dense engine bisimulation against the oracle.
# ---------------------------------------------------------------------------

from repro.core import directory_mn as dmn  # noqa: E402


@pytest.mark.parametrize("R,L", [(8, 16), (33, 8), (64, 32)])
def test_pack_unpack_roundtrip_and_bit_ops(R, L):
    rng = np.random.default_rng(SEED + R)
    mask = jnp.asarray(rng.random((R, L)) < 0.4)
    words = dmn.pack_mask(mask)
    assert words.dtype == jnp.uint32
    assert words.shape == (L, dmn.n_words(R))
    np.testing.assert_array_equal(np.asarray(dmn.unpack_mask(words, R)),
                                  np.asarray(mask))
    if R % 32:
        # pad bits past R are always zero (popcounts stay honest)
        np.testing.assert_array_equal(
            np.asarray(words[..., -1] >> jnp.uint32(R % 32)), 0)
    node = jnp.asarray(rng.integers(0, R, (L,)).astype(np.int32))
    got = dmn.get_bit(words, node)
    want = np.asarray(mask)[np.asarray(node), np.arange(L)]
    np.testing.assert_array_equal(np.asarray(got), want)
    # write_bit(set=do, clear=~do) forces lane `node` to `do` exactly
    do = jnp.asarray(rng.random((L,)) < 0.5)
    w2 = dmn.write_bit(words, do, ~do, node)
    ref = np.asarray(mask).copy()
    ref[np.asarray(node), np.arange(L)] = np.asarray(do)
    np.testing.assert_array_equal(np.asarray(dmn.unpack_mask(w2, R)), ref)


@pytest.mark.parametrize("shape", [(16, 1), (8, 2), (3, 16, 2), (64, 3)])
def test_packed_any_bit_exact(shape):
    rng = np.random.default_rng(SEED)
    w = rng.integers(0, 2 ** 32, shape, dtype=np.uint32)
    w = np.where(rng.random(shape) < 0.5, w, 0).astype(np.uint32)
    words = jnp.asarray(w)
    want = kref.packed_any_ref(words)
    got = coh.packed_any(words, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(kops.packed_any(words)),
                                  np.asarray(want))


@pytest.mark.parametrize("R,L", [(8, 16), (33, 8), (64, 32)])
def test_packed_fanout_bit_exact(R, L):
    rng = np.random.default_rng(SEED + R)
    W = dmn.n_words(R)
    pres = jnp.asarray(dmn.pack_mask(jnp.asarray(rng.random((R, L)) < 0.5)))
    excl = pres & jnp.asarray(
        dmn.pack_mask(jnp.asarray(rng.random((R, L)) < 0.5)))
    node = jnp.asarray(rng.integers(0, R, (L,)).astype(np.int32))
    sh = jnp.asarray(rng.random((L,)) < 0.5)
    ex = jnp.asarray(rng.random((L,)) < 0.5) & ~sh
    want = kref.packed_fanout_ref(pres, excl, node, sh, ex)
    got = coh.packed_fanout(pres, excl, node, sh, ex, interpret=True)
    for g, w in zip(got, want):
        assert g.shape == (L, W)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    for g, w in zip(kops.packed_fanout(pres, excl, node, sh, ex), want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_packed_is_optin_and_dense_default_shares_cache():
    """packed rides the state DTYPE, not a static jit arg: the default
    (dense) engine and a packed engine share the SAME lru-cached jitted
    step — the pre-packing cached program is preserved exactly."""
    from repro.core.engine_mn import _jitted_step_mn
    assert EngineConfig().packed is False
    dense = EngineMN(jnp.zeros((8, 2), jnp.float32), n_remotes=2)
    packed = EngineMN(jnp.zeros((8, 2), jnp.float32), n_remotes=2,
                      packed=True)
    assert dense.packed is False and packed.packed is True
    assert dense._step is packed._step
    assert _jitted_step_mn(dense.subset.name, False, 1, 0) is dense._step
    st = packed.init()
    assert st.hreq_pending.dtype == jnp.uint32
    assert st.dir.view.dtype == jnp.uint32
    W = dmn.n_words(2)
    assert st.dir.view.shape == (2, 8, W)
    assert st.hreq_pending.shape == (2, 8, W)


PACKED_CASES = [(8, 1, True), (33, 2, False), (64, 2, True)]


@pytest.mark.parametrize("R,H,moesi", PACKED_CASES)
def test_packed_stream_bit_identical_and_oracle(R, H, moesi):
    """Full streaming bisimulation, dense vs packed, across word-count
    regimes (W=1, ragged W=2, full W=2) and home counts: counters,
    message counts and retirement traces bit-identical, and the packed
    run's linearization replays into the MultiNodeRef oracle."""
    cfg = StreamConfig(workload=WorkloadSpec("zipfian", ops=16, seed=3),
                       width=2, collect_trace=True)
    base = dict(remotes=R, lines=16, homes=H, moesi=moesi)
    a = run_stream(EngineConfig(**base).build(), cfg)
    b = run_stream(EngineConfig(**base, packed=True).build(), cfg)
    assert a.completed and b.completed
    np.testing.assert_array_equal(a.msg_count, b.msg_count)
    assert a.payload_msgs == b.payload_msgs
    np.testing.assert_array_equal(a.trace.retire_step, b.trace.retire_step)
    for f, (x, y) in zip(a.counters._fields, zip(a.counters, b.counters)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f)
    validate_run(b)


def test_packed_pallas_backend_matches_packed_xla():
    """The packed word kernels dispatch through the same ops contract:
    a packed pallas engine equals the packed xla engine bit-for-bit."""
    cfg = StreamConfig(workload=WorkloadSpec("zipfian", ops=16, seed=11),
                       collect_trace=True)
    a = run_stream(EngineConfig(remotes=8, lines=16, packed=True).build(),
                   cfg)
    b = run_stream(EngineConfig(remotes=8, lines=16, packed=True,
                                kernel_backend="pallas").build(), cfg)
    np.testing.assert_array_equal(a.msg_count, b.msg_count)
    np.testing.assert_array_equal(a.trace.retire_step, b.trace.retire_step)
    for f, (x, y) in zip(a.counters._fields, zip(a.counters, b.counters)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f)


# ---------------------------------------------------------------------------
# Gather-free table lookups and per-line remote selection: the engine's
# TPU-friendly formulations agree with the gathers they replace.
# ---------------------------------------------------------------------------

import dataclasses  # noqa: E402

from repro.core.protocol import (FULL_MOESI, bake, bake_mn,  # noqa: E402
                                 lookup)

TABLES = {f"{kind}.{f.name}": getattr(t, f.name)
          for kind, t in (("base", bake(True)), ("mn", bake_mn(FULL_MOESI)))
          for f in dataclasses.fields(t)
          if isinstance(getattr(t, f.name), np.ndarray)}


@pytest.mark.parametrize("name", sorted(TABLES))
def test_lookup_matches_gather(name):
    """Every index tuple of every protocol table, plus one out-of-range
    index on each side per axis: ``lookup`` returns what the gather
    ``jnp.asarray(table)[idx]`` returns, dtype included."""
    t = TABLES[name]
    axes = [np.arange(-1, d + 1) for d in t.shape]
    idx = [jnp.asarray(a.ravel(), jnp.int32)
           for a in np.meshgrid(*axes, indexing="ij")]
    want = jnp.asarray(t)[tuple(jnp.clip(jnp.where(i < 0, i + d, i), 0,
                                         d - 1)
                                for i, d in zip(idx, t.shape))]
    got = lookup(t, *idx)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("shape", [(5, 16), (2, 3, 8), (4, 8, 3)])
def test_take_remote_matches_take_along_axis(shape):
    """``_take_remote`` picks row ``node[l]`` of each line, for [R, L],
    home-batched [H, R, L] and payload [R, L, B] planes."""
    rng = np.random.default_rng(SEED)
    arr = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    payload = shape == (4, 8, 3)
    R = shape[0] if payload else shape[-2]
    node_shape = shape[-2:-1] if payload else shape[:-2] + shape[-1:]
    node = jnp.asarray(rng.integers(0, R, node_shape).astype(np.int32))
    if payload:
        want = arr[node, jnp.arange(shape[1])]
    else:
        want = jnp.take_along_axis(arr, node[..., None, :],
                                   axis=-2)[..., 0, :]
    np.testing.assert_array_equal(np.asarray(dmn._take_remote(arr, node)),
                                  np.asarray(want))
