"""Histogram folds bucket by compares, and count exactly what the
``searchsorted`` formulation counted.

``counters.bucket_counts`` is the one fold behind the latency, sojourn,
admission-wait and observability histograms: a sample's bucket is the
number of sorted edges at or below it, which is
``searchsorted(edges, x, side="right")`` with no per-element gather.
These tests pin the helper against numpy's ``searchsorted`` on samples
that hit every edge, fall below the first and past the last, and pin an
open-loop run's histograms against the retirement trace and against
the ``searchsorted`` formulation compiled into the same program.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.traffic import (ARRIVALS, LAT_EDGES, SOJOURN_EDGES,
                           AdmissionConfig, EngineConfig, StreamConfig,
                           WORKLOADS, run_stream)
from repro.traffic import driver
from repro.traffic.counters import bucket_counts

R, L, T = 3, 12, 24


def _searchsorted_counts(x, mask, edges, axis):
    """The formulation the folds used before: a ``searchsorted`` bucket,
    then a masked one-hot reduce."""
    bucket = jnp.searchsorted(jnp.asarray(edges), x, side="right")
    onehot = bucket[..., None] == jnp.arange(len(edges) + 1)
    return (onehot & mask[..., None]).sum(axis)


def _samples(rng, edges, shape):
    """Random int32 samples with every edge, every edge ± 1, negatives and
    values past the last edge among them."""
    special = np.concatenate([edges, edges - 1, edges + 1,
                              [np.iinfo(np.int32).min, -1, 0,
                               np.iinfo(np.int32).max]]).astype(np.int32)
    x = rng.integers(-20_000, 20_000, size=shape, dtype=np.int32).ravel()
    x[:special.size] = special
    return rng.permutation(x).reshape(shape)


@pytest.mark.parametrize("edges,shape,axis", [
    (LAT_EDGES, (5, 64), 1),                 # per-remote latency rows
    (SOJOURN_EDGES, (4, 96), (0, 1)),         # sojourn / admission wait
    (LAT_EDGES, (2, 3, 40), (1, 2)),          # stacked observability rows
], ids=["lat_rows", "sojourn", "stacked"])
def test_bucket_counts_equal_numpy_searchsorted(edges, shape, axis):
    rng = np.random.default_rng(11)
    x = _samples(rng, edges, shape)
    mask = rng.random(shape) < 0.7
    got = np.asarray(jax.jit(bucket_counts, static_argnums=(2, 3))(
        jnp.asarray(x), jnp.asarray(mask), tuple(int(e) for e in edges),
        axis))
    bucket = np.searchsorted(edges, x, side="right")
    want = ((bucket[..., None] == np.arange(len(edges) + 1))
            & mask[..., None]).sum(axis)
    np.testing.assert_array_equal(got, want)
    assert got.sum() == mask.sum()


def _open_loop_run(width, admit):
    eng = EngineConfig(remotes=R, lines=L).build()
    sched = ARRIVALS["poisson"](jax.random.key(5), T, R, 0.3)
    cfg = StreamConfig(
        workload=WORKLOADS["zipfian"](jax.random.key(7), T, R, L),
        arrivals=sched, width=width, collect_trace=True,
        admission=AdmissionConfig(max_inflight=2, reserve=1) if admit
        else None)
    return run_stream(eng, cfg), np.asarray(sched.step)


def _searchsorted_run(width, admit):
    """The same run, from a program compiled with the ``searchsorted``
    folds; the program cache is cleared on both sides so neither
    program leaks into another run."""
    driver._jitted_stream.cache_clear()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(driver, "bucket_counts", _searchsorted_counts)
            return _open_loop_run(width, admit)[0]
    finally:
        driver._jitted_stream.cache_clear()


@pytest.mark.parametrize("admit", [False, True], ids=["open", "admission"])
@pytest.mark.parametrize("width", [1, 2])
def test_open_loop_histograms_exact(width, admit):
    run, arrive = _open_loop_run(width, admit)
    assert run.completed
    # sojourn = retirement step - arrival stamp, over the retired ops
    done = run.trace.retire_step >= 0
    soj = (run.trace.retire_step - arrive)[done]
    bucket = np.searchsorted(SOJOURN_EDGES, soj, side="right")
    want = np.bincount(bucket, minlength=len(SOJOURN_EDGES) + 1)
    np.testing.assert_array_equal(run.sojourn_hist, want)
    assert run.admit_wait_hist.sum() == done.sum()
    # bit-identical to the searchsorted formulation of the same program
    ref = _searchsorted_run(width, admit)
    np.testing.assert_array_equal(run.sojourn_hist, ref.sojourn_hist)
    np.testing.assert_array_equal(run.admit_wait_hist, ref.admit_wait_hist)
