"""The copied generators keep the paper's shapes."""

import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench.graphs import synth_dag, table1_variants  # noqa: E402
from bench.lib.pool import PoolMaker  # noqa: E402


def test_synth30_sizes_and_degrees():
    rng = np.random.default_rng([2**33 + 1, 1])
    specs = synth_dag.make(rng, 50, {"n": 30, "degs": [2, 3, 4, 5, 6]})
    assert len(specs) == 50
    assert all(s.n == 30 for s in specs)
    degs = [s.max_in_degree() for s in specs]
    assert set(degs) == {2, 3, 4, 5, 6}
    assert sorted(degs) == sorted([2, 3, 4, 5, 6] * 10)   # balanced blocks
    for s in specs:
        assert all(0 <= u < v for v, ps in enumerate(s.parents) for u in ps)
        assert np.isfinite(s.flops).all() and (s.param_bytes >= 0).all()
    assert len({s.digest() for s in specs}) == 50


def test_table1_variants_keep_table_one():
    rng = np.random.default_rng([7, 1])
    specs = table1_variants.make(rng, 20, {})
    assert sorted(s.model_name for s in specs) == sorted(
        list(table1_variants.MODEL_SPECS) * 2)
    for s in specs:
        v, deg, depth, params, macs, _ = \
            table1_variants.MODEL_SPECS[s.model_name]
        assert (s.n, s.max_in_degree(), s.depth()) == (v, deg, depth)
        assert abs(s.param_bytes.sum() - params) < 1e-6 * params
        assert abs(s.flops.sum() - macs) < 1e-6 * macs
    assert len({s.digest() for s in specs}) == 20


def test_table1_structure_matches_the_program():
    from repro.core import build_model_graph
    for name in table1_variants.MODEL_SPECS:
        parents, names, _, _ = table1_variants.structure(name)
        g = build_model_graph(name)
        assert parents == g.parents and names == g.names


def test_pool_is_the_same_in_workers():
    path = str(ROOT / "bench" / "graphs" / "synth_dag.py")
    args = {"n": 30, "degs": [2, 3, 4, 5, 6]}
    one = PoolMaker(path, args, 5, 1100, workers=0).result()
    two = PoolMaker(path, args, 5, 1100, workers=2).result()
    assert [s.digest() for s in one] == [s.digest() for s in two]
