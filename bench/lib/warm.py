"""Warm every fused serving program that a flush over a pool can reach.

A copy of the warm-set logic of the program's traffic benchmark
(``benchmarks/serve_traffic_bench._warm_program_space``) as it stood when
this benchmark was written, driven through the public
``schedule_many(..., use_cache=False)``.

A program is keyed by (size bucket, batch bucket, child width) and a
static ``dense`` flag (every graph of the sub-batch fills its size bucket).
A sub-batch's child width is the widest of its members', which some member
carries alone, so one representative per (size bucket, child width) at
each power-of-two batch bucket up to ``max_batch`` covers every key; the
dense flag doubles that wherever both variants are reachable.
"""

from __future__ import annotations

MIN_BUCKET = 8
MIN_CHILD_WIDTH = 4


def bucket_for(n: int) -> int:
    return max(MIN_BUCKET, 1 << (n - 1).bit_length())


def child_width(g) -> int:
    widest = max((len(c) for c in g.children), default=1)
    return max(MIN_CHILD_WIDTH, 1 << (max(widest, 1) - 1).bit_length())


def program_calls(pool, max_batch: int) -> dict:
    """{(bucket, batch, width, dense): the graphs of one call that runs
    that program} for every program a flush over ``pool`` can reach."""
    reps = {}        # (bucket, width) -> graph, preferring n < bucket
    dense_reps = {}  # (bucket, width) -> graph with n == bucket
    small = {}       # bucket -> narrowest graph with n < bucket
    for g in pool:
        bk, c = bucket_for(g.n), child_width(g)
        if g.n == bk:
            dense_reps.setdefault((bk, c), g)
            reps.setdefault((bk, c), g)
        else:
            cur = reps.get((bk, c))
            if cur is None or cur.n == bk:
                reps[(bk, c)] = g
            if bk not in small or c < child_width(small[bk]):
                small[bk] = g
    calls = {}
    b = 1
    while b <= max_batch:
        for (bk, c), g in reps.items():
            calls.setdefault((bk, b, c, g.n == bk), [g] * b)
            if (g.n == bk and b > 1 and bk in small
                    and child_width(small[bk]) <= c):
                calls.setdefault((bk, b, c, False),
                                 [g] * (b - 1) + [small[bk]])
        for (bk, c), g in dense_reps.items():
            calls.setdefault((bk, b, c, True), [g] * b)
        b <<= 1
    return calls


class Warmer:
    """Runs each reachable program once, through the public
    ``schedule_many(..., use_cache=False)``.  :meth:`warm` can be called
    on a growing pool: it runs only the programs not yet run."""

    def __init__(self, sched, max_batch: int, n_stages: int, system):
        self.sched = sched
        self.max_batch = max_batch
        self.n_stages = n_stages
        self.system = system
        self.done: set = set()

    def warm(self, pool) -> int:
        """Runs the programs ``pool`` reaches that have not run; returns
        how many it ran."""
        ran = 0
        for key, graphs in program_calls(pool, self.max_batch).items():
            if key not in self.done:
                self.sched.schedule_many(graphs, self.n_stages, self.system,
                                         use_cache=False)
                self.done.add(key)
                ran += 1
        return ran
