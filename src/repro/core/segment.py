"""Device-side rho + repair: the jittable twins of the host scheduling tail.

``schedule`` deploys ``repair(rho(pi))`` after the PtrNet decode; PR 1 still
ran both per graph on the host after every batched decode, which made the
O(n^2 k) segmentation DP and the fixed-point repair the serving bottleneck.
This module holds the XLA-resident twins so the whole cache-miss pipeline —
greedy decode -> contiguous-segmentation DP -> deployment repair — fuses
into ONE jitted, vmapped program per size bucket (:mod:`repro.core.batching`):

* :func:`rho_dp_jax` — the optimal-contiguous-segmentation DP of
  :func:`repro.core.exact.exact_dp`, including its lexicographic
  (bottleneck, latency) tie-break, generalized with ``n_valid`` so a padded
  graph segments *bit-identically* to its unpadded self (padded order
  positions carry zero cost and the per-stage dispatch overhead counts only
  real nodes);
* :func:`dependency_repair_jax` / :func:`co_consumer_repair_jax` /
  :func:`repair_jax` — faithful transcriptions of
  :mod:`repro.core.postprocess` as masked scans over the packed
  parent/child matrices (``CompGraph.parent_matrix`` /
  ``CompGraph.child_matrix``).  All-integer arithmetic, so the device
  output is bit-identical to the numpy reference (property-tested on
  random DAGs).

The same :func:`rho_dp_jax` also computes the training reward of
:mod:`repro.core.rl` (Eq. 3) and the vmapped exact-DP labeler, so training
and serving share one segmentation program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .costmodel import CAPACITY_PENALTY_S, PipelineSystem

__all__ = [
    "rho_dp_jax",
    "exact_dp_jax",
    "exact_dp_batch",
    "dependency_repair_jax",
    "co_consumer_repair_jax",
    "repair_jax",
]


def exact_dp_jax(
    flops,
    param_bytes,
    out_bytes,
    parent_mat,
    n_stages: int,
    system: PipelineSystem,
    n_valid=None,
):
    """Jittable twin of :func:`repro.core.exact.exact_dp` (default order).

    The host exact solver is the contiguous-segmentation DP over the node
    *index* order (topological by :class:`~repro.core.graph.CompGraph`
    construction) — exactly :func:`rho_dp_jax` on the identity order, so
    this shares the DP program (and its lexicographic (bottleneck,
    latency) tie-break discipline) with the serving path and the RL
    reward.  ``n_valid`` marks the real-node prefix of a padded graph;
    the valid-prefix assignment is bit-identical to the host solver's
    (differentially fuzzed over >= 500 random DAGs in
    ``tests/test_eval_oracle.py``).

    Returns ``(assign, bottleneck)`` like :func:`rho_dp_jax`; the
    bottleneck is the f32 DP objective — eval-grade float objectives are
    re-derived on the host from the integer assignment
    (:class:`repro.eval.oracle.ExactOracle`), which is what makes the
    oracle's bottleneck/latency bit-identical to the host reference.
    """
    return rho_dp_jax(None, flops, param_bytes, out_bytes, parent_mat,
                      n_stages, system, n_valid=n_valid)


def exact_dp_batch(flops, param_bytes, out_bytes, parent_mat,
                   n_stages: int, system, n_valid):
    """vmapped pad-aware :func:`exact_dp_jax` over a padded batch.

    All array args carry a leading batch dim (``n_valid`` is ``(B,)``);
    one XLA program solves every graph in the pack exactly — the batched
    device-side oracle under :mod:`repro.eval` and the exact-label filler
    for :class:`repro.core.batching.PaddedGraphBatch`.
    """
    def one(fl, pb, ob, pm, nv):
        return exact_dp_jax(fl, pb, ob, pm, n_stages, system, n_valid=nv)

    return jax.vmap(one)(flops, param_bytes, out_bytes, parent_mat, n_valid)


@jax.named_scope("rho_dp")
def rho_dp_jax(
    order,
    flops,
    param_bytes,
    out_bytes,
    parent_mat,
    n_stages: int,
    system: PipelineSystem,
    n_valid=None,
):
    """Optimal contiguous segmentation of ``order`` -> per-node stage (jnp).

    Mirrors :func:`repro.core.exact.exact_dp` including the lexicographic
    (bottleneck, latency) tie-break, so bottleneck-tied splits resolve the
    same way as the host solver.

    ``n_valid`` (traced scalar) marks the first ``n_valid`` order positions
    as real nodes; padded slots must carry zero flops/param/out bytes and
    occupy the trailing order positions (the pad-aware decode guarantees
    both).  Padded positions then contribute zero cost to every segment —
    including the per-stage dispatch overhead, which counts *real* nodes
    only — so the real-node assignment equals the unpadded DP's.

    ``order=None`` is the identity order (the exact DP).  It skips the
    permutation scatters: a scatter of an iota by itself is refused by
    the TPU compiler.
    """
    n = flops.shape[0]
    k = n_stages
    nv = jnp.asarray(n if n_valid is None else n_valid, jnp.int32)
    if order is None:
        pos = jnp.arange(n, dtype=jnp.int32)
        f_ord, p_ord = flops, param_bytes
    else:
        pos = jnp.zeros(n, jnp.int32).at[order].set(
            jnp.arange(n, dtype=jnp.int32))
        f_ord, p_ord = flops[order], param_bytes[order]
    cf = jnp.concatenate([jnp.zeros(1), jnp.cumsum(f_ord)])
    cp = jnp.concatenate([jnp.zeros(1), jnp.cumsum(p_ord)])

    # boundary bytes: node u crosses boundaries (pos[u], last_child_pos[u]]
    safe_parent = jnp.where(parent_mat >= 0, parent_mat, n)
    child_pos = jnp.broadcast_to(pos[:, None], parent_mat.shape)
    lc = (
        jnp.full(n + 1, -1, jnp.int32)
        .at[safe_parent.reshape(-1)]
        .max(child_pos.reshape(-1))[:n]
    )
    b_idx = jnp.arange(n + 1)[:, None]                       # boundaries
    crossing = (b_idx > pos[None, :]) & (b_idx <= lc[None, :])
    bbytes = jnp.sum(jnp.where(crossing, out_bytes[None, :], 0.0), axis=1)

    i_idx = jnp.arange(n + 1)
    seg_flops = cf[None, :] - cf[:, None]
    seg_params = cp[None, :] - cp[:, None]
    # a segment is "occupied" (pays the dispatch overhead) iff it holds at
    # least one REAL node — trailing padded slots must not re-introduce the
    # overhead an empty host-side segment never pays.
    cnt = jnp.minimum(i_idx, nv)
    occ = (cnt[None, :] - cnt[:, None]) > 0

    # Static (trace-time) per-stage constants, as weak-typed python floats so
    # the uniform path emits the exact pre-vector op sequence.  Uniform
    # systems alias ONE cost table across all k stages — the traced program
    # (and therefore every cached fused executable) is unchanged; per-stage
    # constants stack k tables and the recurrence below indexes its stage's.
    re_np = system.stage_vector("compute_rate") * system.stage_vector("compute_eff")
    bw_np = system.stage_vector("link_bw")
    cache_np = system.stage_vector("cache_bytes")
    cap_np = system.capacity_vector()
    same_cost = bool(
        np.all(re_np == re_np[0]) and np.all(bw_np == bw_np[0]) and np.all(cache_np == cache_np[0])
    )
    same_cap = cap_np is None or bool(np.all(cap_np == cap_np[0]))

    def one_table(s: int) -> jnp.ndarray:
        off = jnp.maximum(0.0, seg_params - float(cache_np[s]))
        c = (
            bbytes[:, None] / float(bw_np[s])
            + seg_flops / float(re_np[s])
            + off / float(bw_np[s])
            + jnp.where(occ, system.fixed_overhead_s, 0.0)
        )
        if cap_np is not None:
            # hard memory budget: over-budget segments cost CAPACITY_PENALTY_S
            # extra (finite, so the lex recurrence still orders infeasible
            # completions) — mirrors exact.segment_cost_tables
            c = c + jnp.where(seg_params > float(cap_np[s]), CAPACITY_PENALTY_S, 0.0)
        return jnp.where(i_idx[:, None] <= i_idx[None, :], c, jnp.inf)

    if same_cost and same_cap:
        tables = [one_table(0)] * k
    else:
        tables = [one_table(s) for s in range(k)]
    cost = tables[0]

    # f_b[j], f_l[j]: best (bottleneck, latency) covering positions [0, j);
    # args[s][j]: the lex-argmin split point, exactly as in exact_dp.
    # Tie tolerance: 1e-6 relative, the f32 analogue of the host's 1e-12 —
    # wide enough that XLA fusion noise (rematerialized cost entries can
    # differ by a few ulps between program variants) cannot flip an exact
    # tie, narrow enough that genuinely distinct segmentations stay apart.
    tol = 1e-6
    f_b = cost[0]
    f_l = cost[0]
    splits = []
    for s in range(1, k):
        cost = tables[s]
        b = jnp.maximum(f_b[:, None], cost)                  # (i, j)
        l = f_l[:, None] + cost
        m = b.min(axis=0)
        elig = b <= m * (1 + tol) + 1e-30
        l_el = jnp.where(elig, l, jnp.inf)
        lmin = l_el.min(axis=0)
        # first split whose latency ties the minimum (banded lex-argmin)
        arg = jnp.argmax(l_el <= lmin * (1 + tol) + 1e-30, axis=0)
        splits.append(arg)
        f_b = b[arg, i_idx]
        f_l = l_el[arg, i_idx]

    # backtrack (k is a static python int)
    assign_pos = jnp.zeros(n, jnp.int32)
    j = jnp.asarray(n, jnp.int32)
    positions = jnp.arange(n, dtype=jnp.int32)
    for s in range(k - 1, 0, -1):
        i = splits[s - 1][j].astype(jnp.int32)
        assign_pos = jnp.where((positions >= i) & (positions < j), s, assign_pos)
        j = i
    if order is None:
        return assign_pos, f_b[n]
    assign = jnp.zeros(n, jnp.int32).at[order].set(assign_pos)
    return assign, f_b[n]


def dependency_repair_jax(anc_mat, assign, n_stages: int):
    """Jittable twin of :func:`repro.core.postprocess.dependency_repair`.

    The host's sequential forward propagation computes, for every node, the
    max clipped stage over its ancestors and itself — so with the ancestor
    closure (``CompGraph.ancestor_matrix``) precomputed at pack time it is
    ONE vectorized masked max-reduce, no sequential scan.  Integer ops
    only: bit-identical.
    """
    out = jnp.clip(assign.astype(jnp.int32), 0, n_stages - 1)
    return jnp.max(jnp.where(anc_mat, out[None, :], 0), axis=1)


def co_consumer_repair_jax(parent_mat, child_mat, assign,
                           param_bytes=None, mem_capacity=None):
    """Jittable twin of :func:`repro.core.postprocess.co_consumer_repair`.

    ``child_mat`` is :meth:`CompGraph.child_matrix` — children in ascending
    index order, -1 padded — so the (statically unrolled) inner loop
    updates children in exactly the host's iteration order (a later
    child's dependency floor may read a co-child updated earlier in the
    same row).  The outer pass over producers stays a scan: the host's
    in-place updates are visible to later rows.

    ``mem_capacity`` (static per-stage byte budget, with ``param_bytes``)
    selects the capacity-aware variant: a pull whose target stage would
    exceed its budget is skipped, with stage loads recomputed from the
    incoming assignment and updated move-by-move in the host's order.
    When it is None the original integer-only program is traced unchanged.
    """
    n = parent_mat.shape[0]
    big = jnp.int32(1 << 30)

    if mem_capacity is None:
        def node_step(out, u):
            ch = child_mat[u]
            valid = ch >= 0
            multi = jnp.sum(valid.astype(jnp.int32)) >= 2
            # earliest child stage, frozen BEFORE this row's updates (host
            # computes it once, before its inner loop)
            earliest = jnp.min(jnp.where(valid, out[ch.clip(0)], big))
            for c in range(child_mat.shape[1]):      # static width: unrolled
                v = ch[c]
                vc = v.clip(0)
                pv = parent_mat[vc]
                lo = jnp.max(jnp.where(pv >= 0, out[pv.clip(0)], 0))
                new = jnp.maximum(earliest, lo)
                out = out.at[vc].set(
                    jnp.where(multi & (v >= 0), new, out[vc]))
            return out, None

        out, _ = jax.lax.scan(node_step, assign.astype(jnp.int32), jnp.arange(n))
        return out

    caps = jnp.asarray(np.asarray(mem_capacity), param_bytes.dtype)
    out0 = assign.astype(jnp.int32)
    loads0 = jnp.zeros(caps.shape[0], param_bytes.dtype).at[out0].add(param_bytes)

    def node_step_cap(carry, u):
        out, loads = carry
        ch = child_mat[u]
        valid = ch >= 0
        multi = jnp.sum(valid.astype(jnp.int32)) >= 2
        earliest = jnp.min(jnp.where(valid, out[ch.clip(0)], big))
        for c in range(child_mat.shape[1]):          # static width: unrolled
            v = ch[c]
            vc = v.clip(0)
            pv = parent_mat[vc]
            lo = jnp.max(jnp.where(pv >= 0, out[pv.clip(0)], 0))
            new = jnp.maximum(earliest, lo)
            old = out[vc]
            pb = param_bytes[vc]
            fits = loads[new] + pb <= caps[new]
            apply = multi & (v >= 0) & ((new == old) | fits)
            moved = apply & (new != old)
            delta = jnp.where(moved, pb, jnp.zeros((), param_bytes.dtype))
            loads = loads.at[old].add(-delta).at[new].add(delta)
            out = out.at[vc].set(jnp.where(apply, new, old))
        return (out, loads), None

    (out, _), _ = jax.lax.scan(node_step_cap, (out0, loads0), jnp.arange(n))
    return out


@jax.named_scope("repair")
def repair_jax(parent_mat, child_mat, anc_mat, assign, n_stages: int,
               max_iters: int = 8, enforce_co_consumer: bool = True,
               param_bytes=None, mem_capacity=None):
    """Jittable twin of :func:`repro.core.postprocess.repair`.

    Alternates the two rules to a fixed point exactly like the host: a
    ``while_loop`` stops as soon as an iteration is a no-op (the host's
    break), bounded by ``max_iters``.  Re-applying a deterministic pass at
    its fixed point is the identity, so under ``vmap`` the masked extra
    iterations on already-converged lanes change nothing.  A static
    ``mem_capacity`` (with ``param_bytes``) threads the capacity guard into
    every co-consumer pass; None traces the original program unchanged.
    """
    out = dependency_repair_jax(anc_mat, assign, n_stages)
    if enforce_co_consumer:
        def cond(state):
            i, _, converged = state
            return (i < max_iters) & ~converged

        def body(state):
            i, out, _ = state
            nxt = dependency_repair_jax(
                anc_mat,
                co_consumer_repair_jax(parent_mat, child_mat, out,
                                       param_bytes=param_bytes,
                                       mem_capacity=mem_capacity),
                n_stages)
            return i + 1, nxt, jnp.all(nxt == out)

        _, out, _ = jax.lax.while_loop(
            cond, body, (jnp.int32(0), out, jnp.asarray(False)))
    return dependency_repair_jax(anc_mat, out, n_stages)
