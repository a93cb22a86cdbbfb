"""Request pools drawn from the seed.

A pool is made in chunks of ``CHUNK`` graphs; chunk ``c`` draws from
``default_rng([seed, 1, c])``, so a pool is the same whichever process
makes it.  Large pools are made by worker processes (``spawn``; they
import only the generator, never JAX), which run ahead of the parent: it
takes the chunks in order as they come, and warms the program on the
first while the workers make the rest.
"""

from __future__ import annotations

import importlib.util
import multiprocessing
import os

import numpy as np

CHUNK = 512


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([abs(int(seed)), *stream])


def _load(path: str):
    spec = importlib.util.spec_from_file_location("bench_pool_gen", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _chunk(job: tuple):
    path, args, seed, c, size = job
    return _load(path).make(rng_for(seed, 1, c), size, args)


class PoolMaker:
    """Makes ``count`` graphs of one generator: :meth:`chunks` yields them
    in order, :meth:`result` waits for all."""

    def __init__(self, generator_path: str, args: dict, seed: int,
                 count: int, workers: int = 0):
        self._jobs = [(generator_path, args, seed, c, min(CHUNK, count - lo))
                      for c, lo in enumerate(range(0, count, CHUNK))]
        self._pool = None
        self._it = None
        if workers > 1 and len(self._jobs) > 1:
            ctx = multiprocessing.get_context("spawn")
            self._pool = ctx.Pool(min(workers, len(self._jobs)))
            self._it = self._pool.imap(_chunk, self._jobs)

    def chunks(self):
        if self._pool is None:
            for job in self._jobs:
                yield _chunk(job)
        else:
            yield from self._it

    def result(self) -> list:
        return [g for ch in self.chunks() for g in ch]

    def close(self) -> None:
        if self._pool is not None:
            self._pool.terminate()
            self._pool.join()


def default_workers() -> int:
    return max(1, min(8, (os.cpu_count() or 2) - 2))
