"""Open-loop arrivals: a Poisson process of ``rate`` ops per remote per
step, conditioned on its count.  Each remote's ``T`` arrivals fall
uniformly over a window of ``ceil(T / rate)`` steps, and the latest
arrival of the point sits on the window's last step.  So the offered load
is ``rate`` and every point gets the same step budget, which is a shape
of the program: no point compiles a program of its own.  (A plain
Poisson draw, as the program's ``repro.traffic.arrivals`` makes, gives
each point its own last arrival.)"""
import math

import numpy as np


def window(T: int, rate: float) -> int:
    if not rate > 0:
        raise ValueError(f"arrival rate must be > 0, got {rate}")
    return int(math.ceil(T / rate))


def generate(rng, T, R, rate):
    """``[T, R]`` arrival steps, sorted per remote."""
    w = window(T, rate)
    steps = np.sort(rng.integers(0, w, (T, R)), axis=0)
    steps[T - 1, rng.integers(R)] = w - 1
    return steps.astype(np.int32)
