"""Plain reference of what a served schedule must be.

Written from the paper's description (arXiv:2304.04716 §III) and kept
apart from the program: it imports nothing of ``repro`` and reads the
policy weights from the release checkpoint's files itself.

* :func:`embed` — the per-node features of §III-A (ASAP level, parents'
  levels and ids, hashed node id, memory column);
* :func:`pointer_readings` — the LSTM pointer network of §III-B (encoder
  LSTM, decoder LSTM, glimpse attention, pointer scores, visited and
  infeasible nodes masked), run teacher-forced along a served order in
  plain ``jax.numpy``.  Per step it returns the best selectable logit, the
  served node's logit and the reference's own argmax.  A release with a
  ``w_sys`` leaf starts its decoder from ``dec0 + profile @ w_sys`` on a
  system that is not uniform (:func:`profile`);
* :func:`rho` and :func:`repair` — the order-to-stages map (optimal
  contiguous segmentation under the Coral pipeline cost model,
  lexicographic (bottleneck, latency)) and the deployment repair
  (dependency push, co-consumer rule), in float64 numpy.

A system is a dict: ``n_stages``, ``fixed_overhead_s``, and each of
``compute_rate``, ``compute_eff``, ``link_bw`` and ``cache_bytes`` as one
number for every stage or a list of ``n_stages`` numbers, one per stage.
An optional ``mem_capacity`` (one number or a list) is a hard budget of
parameter bytes per stage: a segment over it costs
:data:`CAPACITY_PENALTY_S` more, and the repair moves no node onto a stage
it would take over its budget.
"""

from __future__ import annotations

import functools
import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib.graphspec import GraphSpec

_MEM_SCALE = 1.0e6
_ID_MODULUS = 1 << 16

#: added to the time of a stage over its ``mem_capacity``: finite, so that
#: the DP still orders splits that no budget admits and its backtrack stays
#: defined, and beyond any stage time a feasible split can have
CAPACITY_PENALTY_S = 1.0e30
#: width of the system profile the decoder's start token is conditioned on
SYS_FEAT_DIM = 16
_STAGE_KEYS = ("compute_rate", "compute_eff", "link_bw", "cache_bytes")


# --------------------------------------------------------------------- #
# weights and features
# --------------------------------------------------------------------- #
def load_params(release_dir: Path) -> dict:
    """The release's parameter leaves as nested dicts of float32 arrays."""
    pdir = Path(release_dir) / "params"
    manifest = json.loads((pdir / "manifest.json").read_text())
    out: dict = {}
    for leaf in manifest["leaves"]:
        arr = np.frombuffer((pdir / leaf["file"]).read_bytes(),
                            dtype=np.dtype(leaf["dtype"]))
        d = out
        *path, last = leaf["name"].split("/")
        for p in path:
            d = d.setdefault(p, {})
        d[last] = arr.reshape(leaf["shape"]).astype(np.float32)
    return out


def _op_id(name: str) -> int:
    digest = hashlib.sha256(name.encode("utf-8")).digest()
    return int.from_bytes(digest[:4], "little") % _ID_MODULUS


def embed(spec: GraphSpec, max_deg: int = 6) -> np.ndarray:
    """(n, 2 + 2 max_deg + 2) rows: [level, parent levels, parent ids,
    node id, log1p(memory / 1 MB)], levels over depth, ids over 2**16."""
    n = spec.n
    levels = spec.levels().astype(np.float64)
    denom = max(float(levels.max()), 1.0)
    ids = np.array([_op_id(nm) for nm in spec.names], np.float64) / _ID_MODULUS
    feat = np.zeros((n, 2 * max_deg + 4), np.float32)
    feat[:, 0] = levels / denom
    for v, ps in enumerate(spec.parents):
        for j in range(max_deg):
            if j < len(ps):
                feat[v, 1 + j] = levels[ps[j]] / denom
                feat[v, 1 + max_deg + j] = ids[ps[j]]
            else:
                feat[v, 1 + max_deg + j] = -1.0
    feat[:, 1 + 2 * max_deg] = ids
    feat[:, 2 + 2 * max_deg] = np.log1p(
        (spec.param_bytes + spec.out_bytes) / _MEM_SCALE)
    return feat


# --------------------------------------------------------------------- #
# the system
# --------------------------------------------------------------------- #
def stage_vector(system: dict, key: str) -> np.ndarray:
    """``system[key]`` as (n_stages,) float64, one entry per stage."""
    k = int(system["n_stages"])
    v = np.asarray(system[key], np.float64)
    if v.ndim and v.shape != (k,):
        raise ValueError(f"{key} has {v.size} entries for n_stages={k}")
    return np.broadcast_to(v, (k,)).astype(np.float64)


def capacities(system: dict) -> np.ndarray | None:
    """The hard per-stage parameter budget, or None where there is none."""
    if system.get("mem_capacity") is None:
        return None
    return stage_vector(system, "mem_capacity")


def profile(system: dict) -> np.ndarray:
    """The (16,) float32 hardware profile the decoder is conditioned on.

    All zero for the paper's uniform chain: every cost constant one
    number and no memory budget.  Otherwise, for each of rate x
    efficiency, link bandwidth and cache, the min, max and (population)
    std of the per-stage log2 deviation from the stages' geometric mean;
    with a memory budget, a 1 at index 9 and the min, max and std of
    log2(budget / cache) / 8 at 10-12."""
    feats = np.zeros(SYS_FEAT_DIM, np.float32)
    if (all(np.ndim(system[key]) == 0 for key in _STAGE_KEYS)
            and system.get("mem_capacity") is None):
        return feats
    rate_eff = (stage_vector(system, "compute_rate")
                * stage_vector(system, "compute_eff"))
    cache = stage_vector(system, "cache_bytes")
    for i, vec in enumerate((rate_eff, stage_vector(system, "link_bw"),
                             cache)):
        logs = np.log2(vec)
        dev = logs - logs.mean()
        feats[3 * i: 3 * i + 3] = (dev.min(), dev.max(), dev.std())
    caps = capacities(system)
    if caps is not None:
        ratio = np.log2(caps / cache) / 8.0
        feats[9] = 1.0
        feats[10:13] = (ratio.min(), ratio.max(), ratio.std())
    return feats


# --------------------------------------------------------------------- #
# the pointer network, teacher-forced
# --------------------------------------------------------------------- #
def _split_dot(passes: int):
    """A float32 matrix product from bfloat16 passes, as a TPU runs one
    at a lower precision: operands split into bf16 high and low parts;
    3 passes sum hi*hi + hi*lo + lo*hi (``high``), 1 pass hi*hi
    (``default``).  The bf16 products are exact in float32."""
    bf, f32 = jnp.bfloat16, jnp.float32

    def parts(x):
        hi = x.astype(bf).astype(f32)
        return hi, (x - hi).astype(bf).astype(f32)

    def dot(a, b):
        mm = functools.partial(jnp.matmul, precision="highest")
        (ah, al), (bh, bl) = parts(a), parts(b)
        out = mm(ah, bh)
        if passes == 3:
            out = out + mm(ah, bl) + mm(al, bh)
        return out
    return dot


#: matrix products by name: ``highest`` as the configuration states, and
#: the emulated lower precisions the control runs at on any backend
DOTS = {"highest": functools.partial(jnp.matmul, precision="highest"),
        "bf16x3": _split_dot(3), "bf16x1": _split_dot(1)}


def _lstm(p, x, h, c, dot):
    gates = dot(x, p["wx"]) + dot(h, p["wh"]) + p["b"]
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    return jax.nn.sigmoid(o) * jnp.tanh(c), c


def _one_graph(params, feats, padj, n_valid, order, probe, sys_feat, dot):
    """Teacher-forced decode of one padded graph.

    feats (N, F); padj (N, N) 1.0 where column is a parent of row;
    order/probe (N,) int32; sys_feat the (16,) profile, or None for a
    decoder that starts from ``dec0`` alone.  Returns per step: best
    selectable logit, logit of ``order[t]``, whether ``order[t]`` was
    selectable, the first argmax, and the logit of ``probe[t]``.
    """
    N = feats.shape[0]
    emb = dot(feats, params["w_in"]) + params["b_in"]
    hdim = params["enc"]["wh"].shape[0]
    live = jnp.arange(N) < n_valid

    def enc_step(carry, xs):
        h, c = carry
        x, ok = xs
        h2, c2 = _lstm(params["enc"], x, h, c, dot)
        h2, c2 = jnp.where(ok, h2, h), jnp.where(ok, c2, c)
        return (h2, c2), h2

    zero = jnp.zeros(hdim, jnp.float32)
    (h, c), ctx = jax.lax.scan(enc_step, (zero, zero), (emb, live))
    ref_g = dot(ctx, params["glimpse"]["w_ref"])
    ref_p = dot(ctx, params["pointer"]["w_ref"])
    n_par = padj.sum(axis=1)
    vec = lambda x, m: dot(x[None], m)[0]          # (H,) @ (H, K)
    col = lambda m, v: dot(m, v[:, None])[:, 0]    # (N, H) @ (H,)

    def dec_step(carry, xs):
        h, c, d, visited = carry
        served, probed = xs
        h, c = _lstm(params["dec"], d[None], h[None], c[None], dot)
        h, c = h[0], c[0]
        feasible = padj @ visited >= n_par     # exact: small counts
        mask = (visited < 0.5) & live & feasible
        g = col(jnp.tanh(ref_g + vec(h, params["glimpse"]["w_q"])),
                params["glimpse"]["v"])
        attn = jax.nn.softmax(jnp.where(mask, g, -jnp.inf))
        glimpse = vec(attn, ctx)
        logits = col(jnp.tanh(ref_p + vec(glimpse, params["pointer"]["w_q"])),
                     params["pointer"]["v"])
        logits = jnp.where(mask, logits, -jnp.inf)
        out = (jnp.max(logits), logits[served], mask[served],
               jnp.argmax(logits).astype(jnp.int32), logits[probed])
        visited = visited.at[served].set(1.0)
        return (h, c, emb[served], visited), out

    d0 = params["dec0"]
    if sys_feat is not None:
        d0 = d0 + vec(sys_feat, params["w_sys"])
    init = (h, c, d0, jnp.zeros(N, jnp.float32))
    _, outs = jax.lax.scan(dec_step, init, (order, probe))
    return outs


@functools.partial(jax.jit, static_argnames=("precision",))
def _batched(params, feats, padj, n_valid, orders, probes, sys_feat, *,
             precision):
    """``precision`` names a product in :data:`DOTS`, or a JAX matmul
    precision (``high``, ``default``) the backend itself runs."""
    axes = (None, 0, 0, 0, 0, 0, None)
    if precision in DOTS:
        one = functools.partial(_one_graph, dot=DOTS[precision])
        return jax.vmap(one, in_axes=axes)(
            params, feats, padj, n_valid, orders, probes, sys_feat)
    with jax.default_matmul_precision(precision):
        one = functools.partial(_one_graph, dot=jnp.matmul)
        return jax.vmap(one, in_axes=axes)(
            params, feats, padj, n_valid, orders, probes, sys_feat)


def _pad_size(n: int) -> int:
    return max(32, 1 << (n - 1).bit_length())


def pointer_readings(params: dict, specs: list[GraphSpec],
                     orders: list[np.ndarray], probes=None,
                     precision: str = "highest",
                     block_nodes: int = 16384,
                     system: dict | None = None) -> list:
    """Per graph a dict of (n,) arrays: ``best``, ``served``, ``ok``,
    ``argmax``, ``probe`` (logit of ``probes[i][t]``; of the served node
    when ``probes`` is None).  Graphs run in blocks of one padded size N,
    ``block_nodes // N`` graphs to a block.  The decoder is conditioned on
    ``system``'s :func:`profile` where ``params`` has a ``w_sys`` leaf and
    the profile is not all zero."""
    jparams = jax.tree.map(jnp.asarray, params)
    sys_feat = None
    if system is not None and "w_sys" in params:
        prof = profile(system)
        sys_feat = jnp.asarray(prof) if prof.any() else None
    out: list = [None] * len(specs)
    groups: dict[int, list[int]] = {}
    for i, s in enumerate(specs):
        groups.setdefault(_pad_size(s.n), []).append(i)
    for N, idxs in sorted(groups.items()):
        block = max(1, block_nodes // N)
        for lo in range(0, len(idxs), block):
            part = idxs[lo: lo + block]
            B = block
            F = embed(specs[part[0]]).shape[1]
            feats = np.zeros((B, N, F), np.float32)
            padj = np.zeros((B, N, N), np.float32)
            nv = np.zeros(B, np.int32)
            ords = np.zeros((B, N), np.int32)
            prb = np.zeros((B, N), np.int32)
            for r, i in enumerate(part):
                s = specs[i]
                feats[r, : s.n] = embed(s)
                for v, ps in enumerate(s.parents):
                    for u in ps:
                        padj[r, v, u] += 1.0
                nv[r] = s.n
                o = np.asarray(orders[i], np.int64)
                ords[r, : s.n] = np.clip(o, 0, s.n - 1)
                p = o if probes is None else np.asarray(probes[i], np.int64)
                prb[r, : s.n] = np.clip(p, 0, s.n - 1)
            res = _batched(jparams, feats, padj, nv, ords, prb, sys_feat,
                           precision=precision)
            res = [np.asarray(x) for x in res]
            for r, i in enumerate(part):
                n = specs[i].n
                out[i] = {k: v[r, :n] for k, v in zip(
                    ("best", "served", "ok", "argmax", "probe"), res)}
    return out


def logit_gaps(readings: dict, order: np.ndarray, n: int) -> np.ndarray:
    """Per step: how far the served node's logit lies below the best
    selectable one (inf where the served node was not selectable, or the
    order is not a permutation of the graph's nodes)."""
    order = np.asarray(order)
    if order.shape != (n,) or sorted(order.tolist()) != list(range(n)):
        return np.full(max(n, 1), np.inf)
    gap = readings["best"].astype(np.float64) - readings["served"]
    return np.where(readings["ok"], gap, np.inf)


# --------------------------------------------------------------------- #
# rho and repair
# --------------------------------------------------------------------- #
def _children(spec: GraphSpec) -> list[list[int]]:
    return spec.children()


def _cost_tables(bbytes, seg_flops, seg_params, occupied, system: dict,
                 cost_dtype) -> list[np.ndarray]:
    """One (n + 1, n + 1) table per stage: entry [i, j] is the time of the
    stage holding order positions [i, j).  Where every stage has the same
    constants, the stages share one table.  Every table would hold the
    same numbers; building one instead of ``n_stages`` halves the time of
    :func:`schedule` on a Table-I graph, which every run's check pays."""
    k = int(system["n_stages"])
    ovh = float(system["fixed_overhead_s"])
    rate = (stage_vector(system, "compute_rate")
            * stage_vector(system, "compute_eff"))
    bw = stage_vector(system, "link_bw")
    cache = stage_vector(system, "cache_bytes")
    caps = capacities(system)

    def table(s: int) -> np.ndarray:
        cost = (bbytes[:, None] / bw[s] + seg_flops / rate[s]
                + np.maximum(0.0, seg_params - cache[s]) / bw[s]
                + np.where(occupied, ovh, 0.0))
        if caps is not None:
            cost = cost + np.where(seg_params > caps[s],
                                   CAPACITY_PENALTY_S, 0.0)
        cost[seg_flops < 0] = np.inf
        if cost_dtype is not None:
            cost = cost.astype(cost_dtype).astype(np.float64)
        return cost

    same = all(np.all(v == v[0]) for v in (rate, bw, cache)) and (
        caps is None or np.all(caps == caps[0]))
    return [table(0)] * k if same else [table(s) for s in range(k)]


def rho(spec: GraphSpec, order: np.ndarray, system: dict,
        cost_dtype=None) -> np.ndarray:
    """Optimal contiguous segmentation of ``order`` into
    ``system["n_stages"]`` stages; per-node stage assignment.

    Time of stage s = crossing bytes / link_bw[s] + flops / (rate[s] *
    eff[s]) + max(0, params - cache[s]) / link_bw[s] + fixed overhead if
    occupied, + :data:`CAPACITY_PENALTY_S` if params exceed
    mem_capacity[s].  Stage s of the DP reads stage s's table.  Among
    splits within a relative 1e-12 of the least bottleneck, the least
    latency wins, and among those the first split point.  Costs are
    float64; ``cost_dtype`` rounds each cost table to a lower precision
    first (the control)."""
    n = spec.n
    k = int(system["n_stages"])
    order = np.asarray(order, np.int64)
    pos = np.empty(n, np.int64)
    pos[order] = np.arange(n)
    hi = np.full(n, -1, np.int64)
    for u, cs in enumerate(_children(spec)):
        for v in cs:
            hi[u] = max(hi[u], pos[v])
    b_idx = np.arange(n + 1)[:, None]
    crossing = (b_idx > pos[None, :]) & (b_idx <= hi[None, :])
    bbytes = np.where(crossing, spec.out_bytes[None, :], 0.0).sum(axis=1)

    flops = np.concatenate([[0.0], np.cumsum(spec.flops[order])])
    params = np.concatenate([[0.0], np.cumsum(spec.param_bytes[order])])
    seg_flops = flops[None, :] - flops[:, None]
    seg_params = params[None, :] - params[:, None]
    occupied = (np.arange(n + 1)[None, :] - np.arange(n + 1)[:, None]) > 0
    tables = _cost_tables(bbytes, seg_flops, seg_params, occupied, system,
                          cost_dtype)

    f_b = tables[0][0].copy()
    f_l = tables[0][0].copy()
    args = np.zeros((k, n + 1), np.int64)
    cols = np.arange(n + 1)
    with np.errstate(invalid="ignore"):
        for s in range(1, k):
            cost = tables[s]
            b = np.maximum(f_b[:, None], cost)
            lat = f_l[:, None] + cost
            m = b.min(axis=0)
            elig = b <= m[None, :] * (1 + 1e-12) + 1e-30
            l_el = np.where(elig, lat, np.inf)
            lmin = l_el.min(axis=0)
            arg = (l_el <= lmin[None, :] * (1 + 1e-12) + 1e-30).argmax(axis=0)
            args[s] = arg
            f_b, f_l = b[arg, cols], l_el[arg, cols]
    assign_pos = np.empty(n, np.int64)
    j = n
    for s in range(k - 1, -1, -1):
        i = int(args[s, j]) if s > 0 else 0
        assign_pos[i:j] = s
        j = i
    assign = np.empty(n, np.int64)
    assign[order] = assign_pos
    return assign


def _dependency_push(spec: GraphSpec, assign: np.ndarray, k: int) -> np.ndarray:
    out = np.clip(np.asarray(assign, np.int64), 0, k - 1)
    for v, ps in enumerate(spec.parents):
        for u in ps:
            if out[u] > out[v]:
                out[v] = out[u]
    return out


def repair(spec: GraphSpec, assign: np.ndarray, k: int,
           max_iters: int = 8, caps: np.ndarray | None = None) -> np.ndarray:
    """Dependency push, then the co-consumer rule (every child of a
    multi-consumer node pulled to the earliest child stage its parents
    allow) alternated with the push to a fixed point (at most
    ``max_iters`` rounds), then a last push.

    With ``caps``, the per-stage parameter budget, the co-consumer rule
    skips a pull that would take its target stage over budget.  Stage
    loads are counted afresh from the assignment each round enters with,
    and follow each move, producers in index order, children in order."""
    children = _children(spec)

    def co_consumer(a):
        out = a.copy()
        if caps is not None:
            loads = np.zeros(k)
            np.add.at(loads, out, spec.param_bytes)
        for u in range(spec.n):
            ch = children[u]
            if len(ch) < 2:
                continue
            earliest = min(out[v] for v in ch)
            for v in ch:
                lo = max((out[p] for p in spec.parents[v]), default=0)
                to = max(earliest, lo)
                if caps is not None and to != out[v]:
                    pb = spec.param_bytes[v]
                    if loads[to] + pb > caps[to]:
                        continue
                    loads[to] += pb
                    loads[out[v]] -= pb
                out[v] = to
        return out

    out = _dependency_push(spec, assign, k)
    for _ in range(max_iters):
        nxt = _dependency_push(spec, co_consumer(out), k)
        if np.array_equal(nxt, out):
            break
        out = nxt
    return _dependency_push(spec, out, k)


def schedule(spec: GraphSpec, order: np.ndarray, system: dict,
             cost_dtype=None) -> np.ndarray:
    """The deployed assignment for a served order: repair(rho(order)),
    the repair held to the system's memory budget."""
    k = int(system["n_stages"])
    return repair(spec, rho(spec, order, system, cost_dtype), k,
                  caps=capacities(system))


def valid_schedule(spec: GraphSpec, assign: np.ndarray, k: int) -> bool:
    """One stage in [0, k) per node, never earlier than a parent's."""
    a = np.asarray(assign)
    if a.shape != (spec.n,) or a.dtype.kind not in "iu":
        return False
    if a.min() < 0 or a.max() >= k:
        return False
    return all(a[u] <= a[v] for v, ps in enumerate(spec.parents) for u in ps)


def objective(spec: GraphSpec, assign: np.ndarray, system: dict
              ) -> tuple[float, float]:
    """(bottleneck, latency) seconds of a schedule on the pipeline: per
    stage, with that stage's constants, the bytes of every tensor crossing
    into it over its link, its flops over rate x efficiency, its
    parameters beyond its cache over its link, the fixed overhead if it
    holds a node, and :data:`CAPACITY_PENALTY_S` if its parameters exceed
    its memory budget."""
    a = np.asarray(assign, np.int64)
    k = int(system["n_stages"])
    params = np.zeros(k)
    flops = np.zeros(k)
    np.add.at(params, a, spec.param_bytes)
    np.add.at(flops, a, spec.flops)
    last = a.copy()
    for v, ps in enumerate(spec.parents):
        for u in ps:
            last[u] = max(last[u], a[v])
    inb = np.zeros(k)
    for u in range(spec.n):
        if last[u] > a[u]:
            inb[a[u] + 1: last[u] + 1] += spec.out_bytes[u]
    bw = stage_vector(system, "link_bw")
    occupied = np.zeros(k)
    np.add.at(occupied, a, 1.0)
    t = (inb / bw + flops / (stage_vector(system, "compute_rate")
                             * stage_vector(system, "compute_eff"))
         + np.maximum(0.0, params - stage_vector(system, "cache_bytes")) / bw
         + np.where(occupied > 0, float(system["fixed_overhead_s"]), 0.0))
    caps = capacities(system)
    if caps is not None:
        t = t + np.where(params > caps, CAPACITY_PENALTY_S, 0.0)
    return float(t.max()), float(t.sum())
