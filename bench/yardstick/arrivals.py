"""Request generators: an open-loop Poisson schedule and Zipf node draws.

The schedule gives every seed the same work: the same number of arrivals
and the same set of inter-arrival gaps (drawn once from `gap_seed`, scaled
so that the last arrival lands at the end of the window), in an order the
run's seed permutes. Runs of one cell then differ in which nodes are asked
for and when, not in how much is asked.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """A generator for one named use of the run's seed (any size of int)."""
    return np.random.default_rng([int(stream), int(seed) & (2**63 - 1)])


def poisson_schedule(rate: float, seconds: float, *, seed: int,
                     gap_seed: int = 0) -> np.ndarray:
    """Intended arrival times in [0, seconds] of round(rate * seconds)
    requests, with exponential gaps."""
    n = max(int(round(rate * seconds)), 1)
    gaps = np.random.default_rng(gap_seed).exponential(1.0 / rate, n)
    gaps *= seconds / gaps.sum()
    gaps = seed_rng(seed, 1).permutation(gaps)
    return np.cumsum(gaps)


def uniform_nodes(ids: np.ndarray, n: int, *, seed: int) -> np.ndarray:
    """`n` node ids drawn uniformly, with replacement, from `ids`."""
    return seed_rng(seed, 2).choice(np.asarray(ids), size=n)


def zipf_requests(ids: np.ndarray, n_requests: int, *,
                  exponent: float = 1.0, seed: int = 0) -> np.ndarray:
    """Seeded Zipf(`exponent`) request stream over `ids`: a seeded
    permutation of `ids` assigns popularity ranks, then requests are drawn
    i.i.d. with p(rank k) proportional to k^-exponent. `exponent=0` is
    uniform traffic."""
    ids = np.asarray(ids)
    if ids.ndim != 1 or len(ids) == 0:
        raise ValueError(f"ids must be a non-empty 1-D array, got shape "
                         f"{ids.shape}")
    if exponent < 0:
        raise ValueError(f"exponent must be >= 0, got {exponent}")
    rng = seed_rng(seed, 3)
    ranked = rng.permutation(ids)
    p = np.arange(1, len(ids) + 1, dtype=np.float64) ** -exponent
    p /= p.sum()
    return rng.choice(ranked, size=n_requests, p=p)


def lateness(intended: np.ndarray, submitted: np.ndarray) -> Tuple[float, float]:
    """(p50, max) seconds by which the generator submitted after the
    intended time."""
    late = np.asarray(submitted) - np.asarray(intended)
    if not len(late):
        return 0.0, 0.0
    return float(np.median(late)), float(late.max())
