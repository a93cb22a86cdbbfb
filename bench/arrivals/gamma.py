"""Open loop, bursty arrivals: a renewal process with Gamma-distributed
gaps of mean ``1 / rate_per_s`` and shape ``shape``.  A shape under 1
gives bursts (coefficient of variation ``1 / sqrt(shape)``), the form
BurstGPT (arXiv:2401.17644) fits to served request streams; a shape of
1 is Poisson.  Gaps are stratified over ``block_s`` blocks as in
:mod:`poisson`.
"""

from __future__ import annotations

import numpy as np

from bench.arrivals.poisson import stratified_dues

LOOP = "open"
KEYS = {"rate_per_s", "block_s", "shape"}


def requests(traffic: dict, seed: int, seconds: float):
    from scipy.stats import gamma
    rate = float(traffic["rate_per_s"])
    block_s = float(traffic["block_s"])
    shape = float(traffic["shape"])
    m = max(1, round(rate * block_s))
    gaps = gamma.ppf((np.arange(m) + 0.5) / m, shape)
    dues = stratified_dues(gaps, rate, block_s, seed, seconds)
    return len(dues), dues
