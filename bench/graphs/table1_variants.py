"""Deployment variants of the ten ImageNet graphs of RESPECT's Table I
(arXiv:2304.04716, Table I).

The structures are a copy of the program's ``core/dnn_graphs`` builders as
they stood when the benchmark was written: |V|, deg(V) and depth match
Table I, and each model keeps its published input resolution and MAC
count.  A variant redraws only the per-node split of the published
parameter count (and so the per-node MACs) from the request's random
stream, so every request is a distinct graph with Table I's shape.  The
split changes no size bucket, child width or compiled program, and not
the decode's or the stage DP's work: it moves the costs that the stage
split weighs, and with them how many rounds the repair takes to settle.

``make`` cycles through the ten models in blocks shuffled by the seed, so
every seed serves the same mix of sizes in another order.
"""

from __future__ import annotations

import numpy as np

from bench.lib.graphspec import GraphSpec

# model: (V, deg, depth, params_int8_bytes, mac_ops, input_hw)
MODEL_SPECS: dict[str, tuple[int, int, int, float, float, int]] = {
    "Xception":          (134, 2, 125, 22.9e6, 8.4e9, 299),
    "ResNet50":          (177, 2, 168, 25.6e6, 4.1e9, 224),
    "ResNet101":         (347, 2, 338, 44.7e6, 7.8e9, 224),
    "ResNet152":         (517, 2, 508, 60.4e6, 11.5e9, 224),
    "DenseNet121":       (429, 2, 428, 8.1e6, 2.9e9, 224),
    "ResNet101v2":       (379, 2, 371, 44.7e6, 7.8e9, 224),
    "ResNet152v2":       (566, 2, 558, 60.4e6, 11.5e9, 224),
    "DenseNet169":       (597, 2, 596, 14.3e6, 3.4e9, 224),
    "DenseNet201":       (709, 2, 708, 20.2e6, 4.3e9, 224),
    "InceptionResNetv2": (782, 4, 571, 55.9e6, 13.2e9, 299),
}


def _stage_profile(pos: float, input_hw: int) -> tuple[int, int]:
    """(spatial, channels) at relative depth ``pos`` in [0, 1]."""
    stage = min(int(pos * 5), 4)
    hw = max(input_hw // 2 ** (stage + 1), 7)
    ch = 64 * 2**stage
    return hw, ch


def _plan_branches(v: int, deg: int, depth: int) -> list[tuple[int, list[int]]]:
    """Off-chain branches as (merge chain position, branch lengths)."""
    extra = v - depth
    plans: list[tuple[int, list[int]]] = []
    if extra <= 0:
        return plans
    if deg <= 2:
        step = max((depth - 4) // extra, 1)
        for i in range(extra):
            merge = min(3 + i * step, depth - 1)
            plans.append((merge, [1]))
        return plans
    lengths_cycle = [1, 2, 2, 3][: deg - 1]
    per_module = sum(lengths_cycle)
    n_modules = extra // per_module
    rem = extra - n_modules * per_module
    step = max((depth - 8) // max(n_modules + rem, 1), 1)
    merge = 5
    for _ in range(n_modules):
        plans.append((min(merge, depth - 1), list(lengths_cycle)))
        merge += step
    for _ in range(rem):
        plans.append((min(merge, depth - 1), [1]))
        merge += step
    return plans


def structure(name: str) -> tuple[list[list[int]], list[str], np.ndarray,
                                   np.ndarray]:
    """(parents, names, relative depth per node, merge flag per node)."""
    v, deg, depth, _, _, _ = MODEL_SPECS[name]
    branches_at: dict[int, list[int]] = {}
    for merge, lengths in _plan_branches(v, deg, depth):
        branches_at.setdefault(merge, []).extend(lengths)
    for merge in list(branches_at):
        while len(branches_at[merge]) > deg - 1:
            ln = branches_at[merge].pop()
            alt = merge
            while alt in branches_at and len(branches_at[alt]) >= deg - 1:
                alt = alt + 1 if alt + 1 < depth else 3
            branches_at.setdefault(alt, []).append(ln)

    parents: list[list[int]] = []
    names: list[str] = []
    is_merge: list[bool] = []
    pos_of: list[float] = []
    chain_idx: list[int] = []
    for p in range(depth):
        rel = p / max(depth - 1, 1)
        branch_parents: list[int] = []
        for ln in branches_at.get(p, []):
            anchor_pos = max(p - ln - 1, 0)
            prev = chain_idx[anchor_pos] if chain_idx else 0
            for b in range(ln):
                parents.append([prev] if p > 0 else [])
                names.append(f"{name}/branch{p}_{b}_conv")
                is_merge.append(False)
                pos_of.append(rel)
                prev = len(parents) - 1
            branch_parents.append(prev)
        ps = ([chain_idx[p - 1]] if p > 0 else []) + branch_parents
        parents.append(ps)
        merge_node = len(ps) > 1
        names.append(f"{name}/{'merge' if merge_node else 'conv'}_{p}")
        is_merge.append(merge_node)
        pos_of.append(rel)
        chain_idx.append(len(parents) - 1)
    if len(parents) != v:
        raise AssertionError(f"{name}: built {len(parents)} nodes, want {v}")

    if deg == 2:            # residual identity skips (no depth change)
        budget = depth // 8
        for p in range(4, depth - 3, max(depth // max(budget, 1), 1)):
            tgt = chain_idx[p]
            if len(parents[tgt]) < deg:
                src = chain_idx[p - 2]
                if src not in parents[tgt]:
                    parents[tgt].append(src)
    for ps in parents:
        ps.sort()
    return parents, names, np.asarray(pos_of), np.asarray(is_merge)


def variant(name: str, rng: np.random.Generator, built=None) -> GraphSpec:
    """One deployment variant of ``name``: the parameter split redrawn
    from ``rng``."""
    parents, names, pos, is_merge = built or structure(name)
    _, _, _, total_params, total_macs, input_hw = MODEL_SPECS[name]
    prof = np.array([_stage_profile(p, input_hw) for p in pos],
                    dtype=np.float64)
    hw, ch = prof[:, 0], prof[:, 1]
    n = len(parents)
    out_bytes = hw * hw * ch
    pweight = np.where(is_merge, 0.0, ch**2 * (0.2 + rng.random(n)))
    param_bytes = pweight / max(pweight.sum(), 1) * total_params
    fweight = np.where(is_merge, out_bytes * 1.0, param_bytes * hw * hw)
    flops = fweight / max(fweight.sum(), 1) * total_macs
    return GraphSpec(parents=[list(ps) for ps in parents], flops=flops,
                     param_bytes=param_bytes, out_bytes=out_bytes,
                     names=list(names), model_name=name)


def make(rng: np.random.Generator, count: int, args: dict) -> list[GraphSpec]:
    """``count`` variants, the ten models cycling in seed-shuffled blocks
    (``args`` is empty)."""
    models = list(MODEL_SPECS)
    built = {m: structure(m) for m in models}
    out = []
    while len(out) < count:
        for m in rng.permutation(models)[: count - len(out)]:
            out.append(variant(str(m), rng, built[m]))
    return out
