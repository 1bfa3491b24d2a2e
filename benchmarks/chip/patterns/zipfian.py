"""Zipf(alpha)-popular lines shared by every remote, the popularity rank
decoupled from the line id by a permutation (a numpy copy of the
program's ``repro.traffic.workloads.zipfian``)."""
import functools

import numpy as np

LOAD, STORE = 1, 2


@functools.lru_cache(maxsize=8)
def _cdf(n_lines: int, alpha: float) -> np.ndarray:
    w = np.arange(1, n_lines + 1, dtype=np.float64) ** -alpha
    return np.cumsum(w) / w.sum()


def generate(rng, T, R, L, alpha=1.2, store_frac=0.3):
    """``(op, line)``, each ``[T, R]``: ``store_frac`` of the ops are
    stores, the rest loads."""
    op = np.where(rng.random((T, R)) < store_frac, STORE,
                  LOAD).astype(np.int8)
    idx = np.searchsorted(_cdf(L, float(alpha)), rng.random((T, R)))
    line = rng.permutation(L)[np.clip(idx, 0, L - 1)]
    return op, line
