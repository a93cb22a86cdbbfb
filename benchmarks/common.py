"""Shared benchmark helpers.

Graph-pool construction lives in :mod:`repro.eval.scenarios` (the eval
grid's single source of truth); the wrappers here exist so every bench
— gap-to-optimal, serving traffic, Table-I stats — scores the SAME
pools instead of each keeping a private copy-pasted sampler.
"""

from __future__ import annotations

import time

import numpy as np


def table1_pool() -> dict:
    """name -> CompGraph for the ten Table-I DNN models."""
    from repro.core import all_model_graphs
    return all_model_graphs()


def traffic_pool(smoke: bool, rng: np.random.Generator):
    """(pool, n_synthetic, n_models): the serving-bench request pool —
    the same graphs the eval grid's ``traffic`` scenario scores for
    gap-to-optimal."""
    from repro.eval.scenarios import traffic_pool as _pool
    return _pool(smoke, rng)


def timeit(fn, *args, repeat: int = 5, warmup: int = 1, **kw) -> float:
    """Median wall-time per call in microseconds."""
    for _ in range(warmup):
        fn(*args, **kw)
    times = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        fn(*args, **kw)
        times.append(time.perf_counter() - t0)
    return float(np.median(times) * 1e6)


def emit(name: str, us_per_call: float, derived: str) -> str:
    line = f"{name},{us_per_call:.1f},{derived}"
    print(line, flush=True)
    return line


def load_agent():
    """(scheduler, trained) — the agent every bench scores: the checked-in
    **trained release checkpoint** (``checkpoints/respect-v*``,
    integrity-verified), else seeded untrained weights with a warning.
    Nothing is picked up from local directories git does not track, so a
    checkout scores exactly the weights its commit holds."""
    from repro.core import RespectScheduler
    sched = RespectScheduler.from_release()   # warns on seeded fallback
    return sched, sched.release is not None
