"""DNN computational-graph embedding (paper §III-A, Fig. 1a step 2).

Per node the paper embeds four components:

1. **absolute coordinates** — the node's ASAP topological level ``T_i``;
2. **relative coordinates** — the parents' topological levels *and* the
   parents' IDs (dependency structure); sources get level 0 and parent id -1;
3. **node ID** — an integer obtained by hashing the operator name;
4. **memory consumption** — the operator's memory footprint.

We emit a fixed-width float matrix ``(n, 2 + 2*max_deg + 2)`` with columns

    [T_i, parentT_1..parentT_D, parentID_1..parentID_D, node_id, mem]

normalized into O(1) ranges (levels by graph depth, ids by the hash modulus,
memory by a fixed byte scale) so one network serves graphs of any size — the
paper's generalizability claim (train on |V|=30, deploy up to |V|=782) relies
on the embedding being size-free.  ``max_deg`` defaults to 6, the largest
complexity in the training mixture.
"""

from __future__ import annotations

from itertools import chain, repeat

import numpy as np

from .graph import CompGraph, hash_op_name

__all__ = ["embed_graph", "embed_dim", "embed_rows", "flat_parents",
           "node_slots", "op_id_block", "PAD_PARENT_ID"]

PAD_PARENT_ID = -1.0
_MEM_SCALE = 1.0e6      # bytes; synthetic + Table-I graphs live around this
_ID_MODULUS = 1 << 16


def embed_dim(max_deg: int = 6) -> int:
    return 2 + 2 * max_deg + 2


def node_slots(ns: np.ndarray, bucket_n: int) -> np.ndarray:
    """Flat slot ``b * bucket_n + v`` of every real node, graph by graph."""
    ns = np.asarray(ns, dtype=np.int64)
    shift = np.arange(len(ns)) * bucket_n - (np.cumsum(ns) - ns)
    return np.arange(int(ns.sum())) + np.repeat(shift, ns)


def flat_parents(
    graphs: list[CompGraph],
    slots: np.ndarray,
    bucket_n: int,
    max_deg: int,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Every graph's parent lists, flattened once.

    ``slots`` is :func:`node_slots` of the graphs.  Returns ``(parent_mat,
    child, parent)``: the ``(B, bucket_n, max_deg)`` int32 parent matrix
    (-1 padded) and, per edge in (graph, node, parent-list) order, the flat
    slots of its child and its parent.  An in-degree above ``max_deg``
    raises :func:`embed_graph`'s ``ValueError``.
    """
    lists = [g.parents for g in graphs]
    in_deg = np.fromiter(map(len, chain.from_iterable(lists)), np.int64,
                         len(slots))
    bad = np.flatnonzero(in_deg > max_deg)
    if len(bad):
        raise ValueError(f"in-degree {in_deg[bad[0]]} exceeds "
                         f"max_deg={max_deg}")
    local = np.fromiter(chain.from_iterable(chain.from_iterable(lists)),
                        np.int64, int(in_deg.sum()))
    child = np.repeat(slots, in_deg)
    rank = np.arange(len(local)) - np.repeat(np.cumsum(in_deg) - in_deg,
                                             in_deg)
    parent_mat = np.full((len(graphs), bucket_n, max_deg), -1,
                         dtype=np.int32)
    parent_mat.reshape(-1, max_deg)[child, rank] = local
    return parent_mat, child, child - child % bucket_n + local


def op_id_block(graphs: list[CompGraph], bucket_n: int,
                slots: np.ndarray) -> np.ndarray:
    """(B, bucket_n) int64 operator-name ids (:func:`hash_op_name`), zero
    padded."""
    ids = np.zeros((len(graphs), bucket_n), dtype=np.int64)
    names = chain.from_iterable(g.names for g in graphs)
    ids.reshape(-1)[slots] = np.fromiter(
        map(hash_op_name, names, repeat(_ID_MODULUS)), np.int64, len(slots))
    return ids


def embed_rows(
    levels: np.ndarray,
    op_ids: np.ndarray,
    mem_bytes: np.ndarray,
    parent_mat: np.ndarray,
    n_valid: np.ndarray,
    mem_scale: float = _MEM_SCALE,
) -> np.ndarray:
    """The paper's feature rows of a whole zero-padded batch at once.

    ``levels`` (ASAP levels), ``op_ids`` and ``mem_bytes`` (parameter plus
    output bytes) are ``(B, N)`` blocks, ``parent_mat`` is ``(B, N, D)``
    (-1 padded); rows past ``n_valid`` come out all zero.  Returns the
    ``(B, N, 2 + 2*D + 2)`` float32 rows; every column is computed in
    float64 and rounded once.
    """
    B, N, D = parent_mat.shape
    levels = levels.astype(np.float64)
    # each node's (level, id) column pair, and a fill row N that the -1
    # parent slots read
    own = np.empty((B, N + 1, 2))
    own[:, :N, 0] = levels / np.maximum(levels.max(axis=1), 1.0)[:, None]
    own[:, :N, 1] = op_ids / _ID_MODULUS
    own[:, N] = (0.0, PAD_PARENT_ID)
    at = (np.where(parent_mat >= 0, parent_mat, N)
          + (N + 1) * np.arange(B)[:, None, None])
    par = own.reshape(-1, 2).take(at, axis=0)                  # (B, N, D, 2)
    par[np.arange(N)[None, :] >= np.asarray(n_valid)[:, None]] = 0.0

    feat = np.zeros((B, N, embed_dim(D)), dtype=np.float32)
    feat[:, :, 0] = own[:, :N, 0]                               # absolute
    feat[:, :, 1:1 + D] = par[..., 0]                           # parent lvl
    feat[:, :, 1 + D:1 + 2 * D] = par[..., 1]                   # parent id
    feat[:, :, 1 + 2 * D] = own[:, :N, 1]                       # node id
    feat[:, :, 2 + 2 * D] = np.log1p(mem_bytes / mem_scale)    # memory
    return feat


def embed_graph(
    graph: CompGraph,
    max_deg: int = 6,
    mem_scale: float = _MEM_SCALE,
) -> np.ndarray:
    """Embed a graph into the paper's per-node feature rows (float32)."""
    slots = np.arange(graph.n)
    pmat, _, _ = flat_parents([graph], slots, graph.n, max_deg)
    ids = op_id_block([graph], graph.n, slots)
    mem = graph.param_bytes + graph.out_bytes
    return embed_rows(graph.levels[None], ids, mem[None], pmat,
                      np.array([graph.n]), mem_scale)[0]
