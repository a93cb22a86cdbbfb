"""The whole-batch pack against a plain per-graph, per-node reference.

``pack_padded`` builds a bucket's arrays with array operations over flat
per-node and per-edge arrays, and its levels and ancestor closure by one of
two paths chosen by shape.  The reference below is the loop-by-loop form
(one graph, one node, one edge at a time); every array must match it bit
for bit, on either path.
"""

import hashlib

import numpy as np
import pytest

from repro.core import sample_dag
from repro.core.batching import PACK_PATHS, pack_padded, pack_path
from repro.core.dnn_graphs import build_model_graph
from repro.core.embedding import (
    PAD_PARENT_ID, embed_dim, embed_graph, flat_parents, node_slots)
from repro.core.graph import CompGraph, hash_op_name

MAX_DEG = 6
MEM_SCALE = 1.0e6
MODULUS = 1 << 16


# ---------------------------------------------------------------- reference
def _ref_hash(name, modulus=MODULUS):
    digest = hashlib.sha256(name.encode("utf-8")).digest()
    return int.from_bytes(digest[:4], "little") % modulus


def _ref_levels(g):
    lv = np.zeros(g.n, dtype=np.int64)
    for v in range(g.n):
        if g.parents[v]:
            lv[v] = 1 + max(lv[u] for u in g.parents[v])
    return lv


def _ref_embed(g, max_deg=MAX_DEG):
    levels = _ref_levels(g).astype(np.float64)
    denom = max(float(levels.max()), 1.0)
    ids = np.array([_ref_hash(nm) for nm in g.names],
                   np.int64).astype(np.float64) / MODULUS
    feat = np.zeros((g.n, embed_dim(max_deg)), dtype=np.float32)
    feat[:, 0] = levels / denom
    for v, ps in enumerate(g.parents):
        if len(ps) > max_deg:
            raise ValueError(
                f"in-degree {len(ps)} exceeds max_deg={max_deg}")
        for j in range(max_deg):
            if j < len(ps):
                feat[v, 1 + j] = levels[ps[j]] / denom
                feat[v, 1 + max_deg + j] = ids[ps[j]]
            else:
                feat[v, 1 + j] = 0.0
                feat[v, 1 + max_deg + j] = PAD_PARENT_ID
    feat[:, 1 + 2 * max_deg] = ids
    feat[:, 2 + 2 * max_deg] = np.log1p(
        (g.param_bytes + g.out_bytes) / MEM_SCALE)
    return feat


def _ref_parent_matrix(g, max_deg=MAX_DEG):
    m = np.full((g.n, max_deg), -1, dtype=np.int32)
    for v, ps in enumerate(g.parents):
        m[v, : len(ps)] = ps
    return m


def _children(g):
    ch = [[] for _ in range(g.n)]
    for v, ps in enumerate(g.parents):
        for u in ps:
            ch[u].append(v)
    return ch


def _ref_child_matrix(g, width):
    m = np.full((g.n, width), -1, dtype=np.int32)
    for u, cs in enumerate(_children(g)):
        if len(cs) > width:
            raise ValueError(f"node {u} has out-degree {len(cs)} > {width}")
        m[u, : len(cs)] = cs
    return m


def _ref_ancestor_matrix(g):
    m = np.zeros((g.n, g.n), dtype=bool)
    for v, ps in enumerate(g.parents):
        m[v, v] = True
        for u in ps:
            m[v] |= m[u]
    return m


def _ref_pack(graphs, bucket_n, child_width=None, decode_only=False,
              labels=None):
    """Every field of ``pack_padded``'s batch, graph by graph."""
    B = len(graphs)
    if child_width is None:
        mc = max(max((len(c) for c in _children(g)), default=0)
                 for g in graphs)
        child_width = 0 if decode_only else max(
            4, 1 << (max(mc, 1) - 1).bit_length())
    anc_n = 0 if decode_only else bucket_n
    out = {
        "feats": np.zeros((B, bucket_n, embed_dim(MAX_DEG)), np.float32),
        "parent_mat": np.full((B, bucket_n, MAX_DEG), -1, np.int32),
        "child_mat": np.full((B, bucket_n, child_width), -1, np.int32),
        "ancestor_mat": np.zeros((B, anc_n, anc_n), bool),
        "flops": np.zeros((B, bucket_n), np.float32),
        "param_bytes": np.zeros((B, bucket_n), np.float32),
        "out_bytes": np.zeros((B, bucket_n), np.float32),
        "n_valid": np.zeros(B, np.int32),
    }
    if labels is not None:
        out["label_assign"] = np.zeros((B, bucket_n), np.int32)
        out["label_order"] = np.zeros((B, bucket_n), np.int32)
    for i, g in enumerate(graphs):
        out["feats"][i, : g.n] = _ref_embed(g)
        out["parent_mat"][i, : g.n] = _ref_parent_matrix(g)
        if not decode_only:
            out["child_mat"][i, : g.n] = _ref_child_matrix(g, child_width)
            out["ancestor_mat"][i, : g.n, : g.n] = _ref_ancestor_matrix(g)
        out["flops"][i, : g.n] = g.flops
        out["param_bytes"][i, : g.n] = g.param_bytes
        out["out_bytes"][i, : g.n] = g.out_bytes
        out["n_valid"][i] = g.n
        if labels is not None:
            out["label_assign"][i, : g.n] = labels[0][i]
            out["label_order"][i, : g.n] = labels[1][i]
    return out, all(g.n == bucket_n for g in graphs)


def _assert_batch_equal(batch, graphs, bucket_n, **kw):
    want, dense = _ref_pack(graphs, bucket_n, **kw)
    for name, arr in want.items():
        got = np.asarray(getattr(batch, name))
        assert got.dtype == arr.dtype, name
        assert np.array_equal(got, arr), name
    assert batch.dense is dense
    if "labels" not in kw:
        assert batch.label_assign is None and batch.label_order is None


def _fresh(graphs):
    """The same graphs with no lazy caches filled, as a served request."""
    return [CompGraph([list(p) for p in g.parents], g.flops, g.param_bytes,
                      g.out_bytes, list(g.names), g.model_name)
            for g in graphs]


def _synthetic(seed, count, n_lo, n_hi):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(count):
        n = int(rng.integers(n_lo, n_hi + 1))
        out.append(sample_dag(rng, n=n,
                              deg=int(rng.integers(1, min(6, n - 1) + 1))))
    return out


# -------------------------------------------------------------------- tests
@pytest.mark.parametrize("path", sorted(PACK_PATHS))
@pytest.mark.parametrize("bucket_n, count, n_lo, n_hi", [
    (8, 3, 5, 8), (64, 16, 5, 64), (64, 2, 40, 64), (256, 3, 5, 64),
    (1024, 2, 5, 64)])
def test_levels_and_closure_paths_match_the_reference(
        path, bucket_n, count, n_lo, n_hi):
    graphs = _synthetic(bucket_n + count, count, n_lo, n_hi)
    levels_of, closure_of = PACK_PATHS[path]
    ns = np.array([g.n for g in graphs])
    slots = node_slots(ns, bucket_n)
    pmat, _, _ = flat_parents(graphs, slots, bucket_n, MAX_DEG)
    levels = np.asarray(levels_of(_fresh(graphs), pmat, slots))
    closure = closure_of(_fresh(graphs), pmat, slots)
    want_lv = np.zeros((len(graphs), bucket_n), np.int64)
    want_anc = np.zeros((len(graphs), bucket_n, bucket_n), bool)
    for i, g in enumerate(graphs):
        want_lv[i, : g.n] = _ref_levels(g)
        want_anc[i, : g.n, : g.n] = _ref_ancestor_matrix(g)
    assert levels.dtype == np.int64 and np.array_equal(levels, want_lv)
    assert closure.dtype == bool and np.array_equal(closure, want_anc)


@pytest.mark.parametrize("case", [
    "synthetic-16x30", "mixed-sizes", "padded-batch", "decode-only",
    "labels", "dense", "small-batch", "table1-smallest-two"])
def test_pack_matches_the_reference(case):
    kw = {}
    if case == "table1-smallest-two":
        graphs = [build_model_graph("Xception"),
                  build_model_graph("ResNet50")]
    elif case == "synthetic-16x30":
        graphs = _synthetic(1, 16, 30, 30)
    elif case == "dense":
        graphs = _synthetic(2, 8, 32, 32)
    elif case == "small-batch":
        graphs = _synthetic(3, 3, 5, 40)
    else:
        graphs = _synthetic(4, 12, 5, 64)
    if case == "decode-only":
        kw["decode_only"] = True
    if case == "labels":
        rng = np.random.default_rng(5)
        kw["labels"] = ([rng.integers(0, 4, g.n) for g in graphs],
                        [rng.permutation(g.n) for g in graphs])
        kw["decode_only"] = True
    bucket_n = 64 if case == "mixed-sizes" else None
    batch = pack_padded(_fresh(graphs), bucket_n, **kw)
    _assert_batch_equal(batch, graphs, batch.bucket_n, **kw)
    if case == "padded-batch":
        padded = batch.pad_batch(16)
        rows = len(graphs)
        assert np.array_equal(np.asarray(padded.feats)[:rows],
                              np.asarray(batch.feats))
        assert not np.asarray(padded.feats)[rows:].any()
        assert (np.asarray(padded.parent_mat)[rows:] == -1).all()
        assert not np.asarray(padded.ancestor_mat)[rows:].any()
        assert not np.asarray(padded.n_valid)[rows:].any()


@pytest.mark.parametrize("seed", range(4))
def test_embed_graph_matches_the_reference(seed):
    (g,) = _synthetic(10 + seed, 1, 5, 64)
    got = embed_graph(_fresh([g])[0], MAX_DEG)
    assert got.dtype == np.float32
    assert np.array_equal(got, _ref_embed(g))


def _graph(parents):
    n = len(parents)
    return CompGraph(parents, np.ones(n), np.ones(n), np.ones(n))


WIDE_IN = _graph([[]] * 7 + [list(range(7))])        # node 7: in-degree 7
WIDE_OUT = _graph([[]] + [[0]] * 5)                  # node 0: out-degree 5


@pytest.mark.parametrize("graphs, kw, message", [
    ([WIDE_IN], {}, "in-degree 7 exceeds max_deg=6"),
    ([WIDE_IN], {"decode_only": True}, "in-degree 7 exceeds max_deg=6"),
    ([WIDE_OUT], {"child_width": 4}, "node 0 has out-degree 5 > 4"),
    ([WIDE_IN, WIDE_OUT], {"child_width": 4},
     "in-degree 7 exceeds max_deg=6"),
    ([_graph([[]]), WIDE_OUT], {"child_width": 2},
     "node 0 has out-degree 5 > 2"),
])
def test_degree_errors_match_the_per_graph_builders(graphs, kw, message):
    with pytest.raises(ValueError, match=message):
        pack_padded(graphs, 8, MAX_DEG, **kw)
    with pytest.raises(ValueError, match="in-degree 7 exceeds max_deg=6"):
        embed_graph(WIDE_IN, MAX_DEG)


def test_decode_only_with_a_narrow_child_width_skips_the_children():
    batch = pack_padded([WIDE_OUT], 8, MAX_DEG, child_width=4,
                        decode_only=True)
    assert (np.asarray(batch.child_mat) == -1).all()
    assert np.asarray(batch.ancestor_mat).shape == (1, 0, 0)


@pytest.mark.parametrize("bucket_n, batch, path", [
    (32, 16, "batched"), (32, 8, "batched"), (32, 4, "per_graph"),
    (256, 1, "per_graph"), (256, 2, "per_graph"), (512, 2, "per_graph"),
    (1024, 1, "per_graph"), (1024, 16, "per_graph"), (512, 16, "batched")])
def test_pack_path_by_shape(bucket_n, batch, path):
    assert pack_path(bucket_n, batch) == path


@pytest.mark.parametrize("name, modulus", [
    ("op_0", MODULUS), ("op_29", MODULUS), ("conv2d_17/BiasAdd", MODULUS),
    ("dense", 97), ("", MODULUS), ("naïve/ünïcode", 1 << 20)])
def test_memoised_op_ids_equal_the_sha256_formula(name, modulus):
    assert hash_op_name(name, modulus) == _ref_hash(name, modulus)
    assert hash_op_name(name, modulus) == _ref_hash(name, modulus)  # cached
    assert hash_op_name.__wrapped__(name, modulus) == _ref_hash(name,
                                                                 modulus)
