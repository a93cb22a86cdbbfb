"""Repeat traffic: ``pool_size`` distinct graphs, requested with Zipfian
popularity of constant ``zipf_theta`` (YCSB's is 0.99) over a static hot
set: popularity rank ``r`` maps to graph ``perm[r]``, a permutation drawn
from the seed.  A repeat submits the same graph object again, as a
client that re-requests a model it holds."""

from __future__ import annotations

import numpy as np

from bench.lib.pool import rng_for

KEYS = {"pool_size", "zipf_theta"}


def plan(traffic: dict, seed: int, n_requests: int):
    """(graphs to make, the graph of each request)."""
    size = int(traffic["pool_size"])
    theta = float(traffic["zipf_theta"])
    p = 1.0 / np.arange(1, size + 1) ** theta
    p /= p.sum()
    rng = rng_for(seed, 5)
    ranks = rng.choice(size, size=n_requests, p=p)
    return size, rng.permutation(size)[ranks]
