"""The benchmark's own graph record, independent of the program's types.

Generators under ``bench/graphs`` return :class:`GraphSpec` objects; the
reference (:mod:`bench.lib.reference`) reads them directly, and
:func:`to_program` hands the same data to the program as its public
``CompGraph`` input type.
"""

from __future__ import annotations

import dataclasses
import hashlib

import numpy as np


@dataclasses.dataclass
class GraphSpec:
    """A DAG whose node indices are already topological (parent < child)."""

    parents: list[list[int]]
    flops: np.ndarray
    param_bytes: np.ndarray
    out_bytes: np.ndarray
    names: list[str]
    model_name: str

    @property
    def n(self) -> int:
        return len(self.parents)

    def children(self) -> list[list[int]]:
        ch: list[list[int]] = [[] for _ in range(self.n)]
        for v, ps in enumerate(self.parents):
            for u in ps:
                ch[u].append(v)
        return ch

    def levels(self) -> np.ndarray:
        """ASAP levels (sources at 0)."""
        lv = np.zeros(self.n, dtype=np.int64)
        for v, ps in enumerate(self.parents):
            if ps:
                lv[v] = 1 + max(lv[u] for u in ps)
        return lv

    def depth(self) -> int:
        return int(self.levels().max()) + 1

    def max_in_degree(self) -> int:
        return max(len(ps) for ps in self.parents)

    def digest(self) -> str:
        """Digest of everything a schedule depends on (structure, costs,
        names), computed here and not by the program."""
        h = hashlib.sha256()
        for ps in self.parents:
            h.update(np.asarray(ps, np.int64).tobytes() + b"|")
        for arr in (self.flops, self.param_bytes, self.out_bytes):
            h.update(np.ascontiguousarray(arr, np.float64).tobytes())
        h.update("\x00".join(self.names).encode())
        return h.hexdigest()


def to_program(spec: GraphSpec):
    """The program's ``CompGraph`` for this spec (its public input type)."""
    from repro.core import CompGraph
    return CompGraph(parents=spec.parents, flops=spec.flops,
                     param_bytes=spec.param_bytes, out_bytes=spec.out_bytes,
                     names=spec.names, model_name=spec.model_name)
