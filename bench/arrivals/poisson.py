"""Open loop, Poisson arrivals at ``rate_per_s``.

The gaps are the exponential distribution's quantiles, stratified over
blocks of ``block_s`` seconds: each block holds ``rate_per_s * block_s``
gaps at the quantiles ``(j + 0.5) / m``, scaled so that the block lasts
exactly ``block_s``, in an order shuffled by the seed.  So every seed
offers the same arrivals, second by second, in another order.
"""

from __future__ import annotations

import numpy as np

from bench.lib.pool import rng_for

LOOP = "open"
KEYS = {"rate_per_s", "block_s"}


def quantile_gaps(m: int) -> np.ndarray:
    """``m`` unit-mean exponential gaps at stratified quantiles."""
    q = (np.arange(m) + 0.5) / m
    return -np.log1p(-q)


def stratified_dues(gaps: np.ndarray, rate: float, block_s: float,
                    seed: int, seconds: float) -> np.ndarray:
    """Due times (s from the window's start) of ``blocks`` shuffled copies
    of ``gaps``, each block scaled to last ``block_s``."""
    per_block = len(gaps)
    blocks = max(1, round(rate * seconds / per_block))
    unit = gaps * (block_s / gaps.sum())
    rng = rng_for(seed, 2)
    all_gaps = np.concatenate([rng.permutation(unit) for _ in range(blocks)])
    return np.concatenate([[0.0], np.cumsum(all_gaps)[:-1]])


def requests(traffic: dict, seed: int, seconds: float):
    """(number of requests, their due times)."""
    rate = float(traffic["rate_per_s"])
    block_s = float(traffic["block_s"])
    m = max(1, round(rate * block_s))
    dues = stratified_dues(quantile_gaps(m), rate, block_s, seed, seconds)
    return len(dues), dues
