"""The paper's synthetic training distribution (arXiv:2304.04716, training
data: |V| = 30, deg(V) in {2..6}).

``sample_dag`` is a copy of the program's ``core/sampler.sample_dag`` as it
stood when the benchmark was written, so that a change to the program's
sampler cannot change the benchmark's traffic.  ``make`` draws a request
pool: the max in-degrees cycle through ``degs`` in blocks shuffled by the
seed, so every seed serves the same mix of complexities in another order.
"""

from __future__ import annotations

import numpy as np

from bench.lib.graphspec import GraphSpec


def sample_dag(rng: np.random.Generator, n: int = 30, deg: int = 2,
               chain_frac_range: tuple[float, float] = (0.55, 0.95)
               ) -> GraphSpec:
    """One synthetic computational graph with max in-degree ``deg``."""
    if n < 3:
        raise ValueError("need at least 3 nodes")
    if deg < 1:
        raise ValueError("deg >= 1")

    chain_frac = rng.uniform(*chain_frac_range)
    parents: list[list[int]] = [[] for _ in range(n)]
    indeg = np.zeros(n, dtype=np.int64)

    for v in range(1, n):
        if rng.random() < chain_frac or v == 1:
            parents[v].append(v - 1)           # backbone chain edge
        else:
            u = int(rng.integers(0, v))        # branch start
            parents[v].append(u)
        indeg[v] = 1

    n_extra = int(rng.integers(n // 6, n // 2 + 1))
    candidates = list(range(2, n))
    rng.shuffle(candidates)
    forced = None
    for v in candidates:
        if forced is None and v >= deg:
            forced = v
            want = deg
        else:
            want = int(rng.integers(1, deg + 1))
            if n_extra <= 0:
                continue
        while indeg[v] < want:
            u = int(rng.integers(0, v))
            if u in parents[v]:
                if indeg[v] >= v:               # all predecessors used
                    break
                continue
            parents[v].append(u)
            indeg[v] += 1
            n_extra -= 1

    depth_pos = np.arange(n) / max(n - 1, 1)
    out_bytes = np.exp(rng.normal(0.0, 0.6, n)) * 3e5 * (1.0 - 0.85 * depth_pos)
    param_bytes = np.exp(rng.normal(0.0, 0.9, n)) * 3e5 * (0.3 + 1.7 * depth_pos)
    param_free = rng.random(n) < 0.3
    param_bytes[param_free] = 0.0
    flops = param_bytes * rng.uniform(30, 120, n) + out_bytes * rng.uniform(1, 8, n)

    for ps in parents:
        ps.sort()
    return GraphSpec(parents=parents, flops=flops, param_bytes=param_bytes,
                     out_bytes=out_bytes, names=[f"op_{i}" for i in range(n)],
                     model_name=f"synthetic_n{n}_deg{deg}")


def make(rng: np.random.Generator, count: int, args: dict) -> list[GraphSpec]:
    """``count`` graphs of ``args["n"]`` nodes, degrees in balanced blocks."""
    n = int(args["n"])
    degs = [int(d) for d in args["degs"]]
    out = []
    while len(out) < count:
        block = list(rng.permutation(degs))
        for d in block[: count - len(out)]:
            out.append(sample_dag(rng, n=n, deg=int(d)))
    return out
