"""Batched scheduling engine: size buckets, padded packs, fused bucket fns.

The PtrNet decode is a sequential scan, so scheduling one graph per call
leaves the accelerator idle between tiny dispatches — and PR 1's batched
decode still returned to the host for the O(n^2 k) ``rho`` DP and the
fixed-point ``repair`` per graph.  This module turns a heterogeneous list
of :class:`CompGraph` into a handful of fixed-shape XLA programs that run
the WHOLE miss pipeline on device:

* **size bucketing** — a graph with ``n`` nodes is padded up to the next
  power-of-two bucket (``bucket_for``), so arbitrary request mixes compile
  at most ``log2(n_max)`` programs instead of one per distinct size;
* **padded packing** — :func:`pack_padded` builds embeddings, parent/child
  matrices and the three cost attributes of a whole bucket with array
  operations into a :class:`PaddedGraphBatch` carrying ``n_valid`` per
  graph; the pad-aware decode
  (:mod:`repro.core.ptrnet`) and the ``n_valid``-aware segmentation DP
  (:mod:`repro.core.segment`) guarantee the valid prefix matches the
  unpadded pipeline bit-for-bit;
* **fused decode->rho->repair** — :meth:`BucketedDecoder.fused_schedules`
  runs greedy decode, the contiguous-segmentation DP and the deployment
  repair as ONE jitted vmapped program per bucket; the host only packs
  inputs and slices outputs.  On TPU the decode steps hit the Pallas
  pointer kernel (:mod:`repro.kernels.ptr`) via ``logits_builder``;
* **LRU of compiled fns** — compiled programs are keyed by
  (bucket_n, batch bucket, child width, stages, system) and cold shapes
  are evicted, bounding compile-cache growth under shifting traffic.

The batch dimension is bucketed to powers of two as well (short batches are
padded with ``n_valid = 0`` rows), so a serving loop with fluctuating batch
sizes re-uses the same compiled programs.
"""

from __future__ import annotations

import dataclasses
import os
import threading
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from . import ptrnet, segment
from .costmodel import PipelineSystem
from .embedding import embed_rows, flat_parents, node_slots, op_id_block
from .graph import CompGraph

__all__ = [
    "bucket_for",
    "bucketize",
    "PaddedGraphBatch",
    "pack_padded",
    "pack_path",
    "BucketedDecoder",
    "DECODE_IMPLS",
    "DECODE_IMPL_ENV",
    "DECODE_UNROLL",
]

MIN_BUCKET = 8
MIN_CHILD_WIDTH = 4

#: ``pack_path``'s crossover: the smallest batch that takes the batched
#: levels/closure recurrence (buckets above 256 ask for bucket_n / 32)
PACK_BATCHED_MIN = 8

#: scan-path unroll factor for the serving decode programs: identical
#: per-step math (orders are bit-identical), but unrolling cuts the CPU
#: loop-dispatch overhead that dominates hidden<=256 decode steps (the
#: measured cold-miss win on this class of host is ~1.6x).
DECODE_UNROLL = 8

#: decode_impl choices: how a serving program runs the pointing loop.
#: None auto-picks per shape ("kernel" on TPU when the whole-decode
#: kernel supports the bucket, else "scan").
DECODE_IMPLS = (None, "scan", "kernel", "kernel-interpret")

#: env override (lowest precedence below an explicit constructor arg):
#: RESPECT_DECODE_IMPL=scan|kernel|kernel-interpret forces one impl for
#: every BucketedDecoder in the process.
DECODE_IMPL_ENV = "RESPECT_DECODE_IMPL"


def bucket_for(n: int, min_bucket: int = MIN_BUCKET) -> int:
    """Smallest power-of-two >= n (with a floor so tiny graphs share)."""
    if n < 1:
        raise ValueError("graph must have at least one node")
    return max(min_bucket, 1 << (n - 1).bit_length())


def bucketize(
    graphs: list[CompGraph], min_bucket: int = MIN_BUCKET
) -> dict[int, list[int]]:
    """Group graph *indices* by their size bucket (insertion order kept)."""
    buckets: dict[int, list[int]] = {}
    for i, g in enumerate(graphs):
        buckets.setdefault(bucket_for(g.n, min_bucket), []).append(i)
    return buckets


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PaddedGraphBatch:
    """Fixed-shape pack of B graphs padded to a common node count.

    Carries everything the fused decode->rho->repair program consumes:
    embeddings and parent matrices for the decode, the three cost
    attributes for the segmentation DP, and the packed child matrix for
    the co-consumer repair rule.

    The optional ``label_assign``/``label_order`` fields carry exact-solver
    supervision (zero padded past ``n_valid``); they make this the ONE batch
    representation shared by serving (labels absent) and RL training
    (labels present) — see :mod:`repro.core.rl`.

    The optional ``exact_assign``/``exact_bottleneck`` fields carry the
    batched device oracle's own solution of the pack
    (:meth:`repro.eval.oracle.ExactOracle.label_pack` fills them via
    :func:`repro.core.segment.exact_dp_batch`): the per-node exact-DP
    stage assignment (zero past ``n_valid``) and the f32 DP bottleneck
    per graph.  Unlike the imitation labels above, these are *evaluation*
    ground truth — the gap-to-optimal runner scores policies against
    them without ever leaving the padded representation.

    ``dense`` is a STATIC (pytree-aux) flag set at pack time: True iff every
    graph fills ``bucket_n`` exactly.  Consumers use it to skip the
    ``n_valid`` masking machinery entirely for equal-size packs (e.g. the
    paper's fixed |V| = 30 training), which keeps the unified
    representation free on the homogeneous fast path.
    """

    feats: jnp.ndarray        # (B, bucket_n, F) embedding rows, zero padded
    parent_mat: jnp.ndarray   # (B, bucket_n, D) int32, -1 padded
    child_mat: jnp.ndarray    # (B, bucket_n, MC) int32, -1 padded
    ancestor_mat: jnp.ndarray # (B, bucket_n, bucket_n) bool, False padded
    flops: jnp.ndarray        # (B, bucket_n) float32, zero padded
    param_bytes: jnp.ndarray  # (B, bucket_n) float32, zero padded
    out_bytes: jnp.ndarray    # (B, bucket_n) float32, zero padded
    n_valid: jnp.ndarray      # (B,) int32 real node count per graph
    label_assign: jnp.ndarray | None = None  # (B, bucket_n) int32, 0 padded
    label_order: jnp.ndarray | None = None   # (B, bucket_n) int32, 0 padded
    exact_assign: jnp.ndarray | None = None  # (B, bucket_n) int32, 0 padded
    exact_bottleneck: jnp.ndarray | None = None  # (B,) f32 DP objective
    dense: bool = False       # static: all graphs fill bucket_n exactly

    def tree_flatten(self):
        return (self.feats, self.parent_mat, self.child_mat,
                self.ancestor_mat, self.flops, self.param_bytes,
                self.out_bytes, self.n_valid, self.label_assign,
                self.label_order, self.exact_assign,
                self.exact_bottleneck), self.dense

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, dense=aux)

    @property
    def batch(self) -> int:
        return self.feats.shape[0]

    @property
    def bucket_n(self) -> int:
        return self.feats.shape[1]

    @property
    def child_width(self) -> int:
        return self.child_mat.shape[2]

    @property
    def has_labels(self) -> bool:
        return self.label_assign is not None

    @property
    def has_exact(self) -> bool:
        return self.exact_assign is not None

    def with_exact(self, exact_assign, exact_bottleneck) -> "PaddedGraphBatch":
        """A copy carrying the exact-oracle solution of this pack."""
        return dataclasses.replace(
            self, exact_assign=exact_assign, exact_bottleneck=exact_bottleneck)

    def valid_mask(self) -> jnp.ndarray:
        """(B, bucket_n) bool: True on real-node slots."""
        return jnp.arange(self.bucket_n)[None, :] < self.n_valid[:, None]

    def pad_batch(self, bucket_b: int) -> "PaddedGraphBatch":
        """Pad the batch dimension with inert ``n_valid = 0`` rows.

        Padding runs on HOST (numpy): an eager ``jnp.concatenate`` here
        would compile a throwaway XLA kernel per distinct
        ``(batch, pad)`` shape pair, and arrival-timed micro-batches
        produce fresh pairs constantly — the fused program's jit
        boundary transfers the padded arrays in one step regardless.
        """
        pad = bucket_b - self.batch
        if pad < 0:
            raise ValueError(f"batch {self.batch} exceeds bucket {bucket_b}")
        if pad == 0:
            return self

        def _cat(a, fill):
            a = np.asarray(a)
            row = np.full((pad,) + a.shape[1:], fill, a.dtype)
            return np.concatenate([a, row])

        zcat = lambda a: None if a is None else _cat(a, 0)
        with TraceAnnotation("respect.pack.pad"):
            return PaddedGraphBatch(
                feats=_cat(self.feats, 0),
                parent_mat=_cat(self.parent_mat, -1),
                child_mat=_cat(self.child_mat, -1),
                ancestor_mat=_cat(self.ancestor_mat, False),
                flops=_cat(self.flops, 0),
                param_bytes=_cat(self.param_bytes, 0),
                out_bytes=_cat(self.out_bytes, 0),
                n_valid=_cat(self.n_valid, 0),
                label_assign=zcat(self.label_assign),
                label_order=zcat(self.label_order),
                exact_assign=zcat(self.exact_assign),
                exact_bottleneck=zcat(self.exact_bottleneck),
                dense=False,    # inert rows have n_valid = 0
            )


def _child_width_for(max_out_degree: int,
                     min_width: int = MIN_CHILD_WIDTH) -> int:
    """Power-of-two child-matrix width covering the largest out-degree
    (with a floor, so batches with different fan-outs share programs)."""
    return max(min_width, 1 << (max(max_out_degree, 1) - 1).bit_length())


def _parent_rows(parent_mat: np.ndarray) -> np.ndarray:
    """(N, D, B) flat row ``p * B + b`` of each parent slot in an
    ``(N + 1, B, ...)`` node-major block whose row N is the fill that the
    -1 slots read."""
    B, N, _ = parent_mat.shape
    p = np.where(parent_mat >= 0, parent_mat, N).astype(np.int64)
    return np.ascontiguousarray(
        (p * B + np.arange(B)[:, None, None]).transpose(1, 2, 0))


def _levels_batched(graphs, parent_mat, slots):
    """ASAP levels by one recurrence over the node index, across the whole
    batch: parents precede children, so row v reads only rows < v."""
    B, N, _ = parent_mat.shape
    rows = _parent_rows(parent_mat)
    lv = np.full((N + 1, B), -1, dtype=np.int64)
    flat = lv.reshape(-1)
    for v in range(N):
        lv[v] = flat.take(rows[v]).max(axis=0) + 1
    return lv[:N].T


def _closure_batched(graphs, parent_mat, slots):
    """Ancestor closure by the same recurrence: row v is its parents' rows
    or-ed, plus v itself."""
    B, N, _ = parent_mat.shape
    rows = _parent_rows(parent_mat)
    valid = np.zeros(B * N, dtype=bool)
    valid[slots] = True
    valid = valid.reshape(B, N)
    anc = np.zeros((N + 1, B, N), dtype=bool)
    flat = anc.reshape(-1, N)
    for v in range(N):
        row = np.logical_or.reduce(flat.take(rows[v], axis=0), axis=0)
        row[:, v] = valid[:, v]
        anc[v] = row
    return np.ascontiguousarray(anc[:N].transpose(1, 0, 2))


def _levels_per_graph(graphs, parent_mat, slots):
    """ASAP levels graph by graph (:func:`repro.core.graph.asap_levels`)."""
    lv = np.zeros(parent_mat.shape[:2], dtype=np.int64)
    lv.reshape(-1)[slots] = np.concatenate([g.levels for g in graphs])
    return lv


def _closure_per_graph(graphs, parent_mat, slots):
    """Ancestor closure graph by graph, edge by edge
    (:meth:`CompGraph.ancestor_matrix`)."""
    B, N, _ = parent_mat.shape
    amat = np.zeros((B, N, N), dtype=bool)
    for i, g in enumerate(graphs):
        amat[i, : g.n, : g.n] = g.ancestor_matrix()
    return amat


#: how a pack computes its levels and ancestor closure (see pack_path)
PACK_PATHS = {
    "batched": (_levels_batched, _closure_batched),
    "per_graph": (_levels_per_graph, _closure_per_graph),
}


def pack_path(bucket_n: int, batch: int) -> str:
    """The levels/closure path for a ``(bucket_n, batch)`` pack.

    The batched recurrence takes ``bucket_n`` array steps whatever the
    batch, each dearer as batch x bucket grows; the per-graph loops take a
    Python step per node and edge of every graph.  On the host the
    recurrence wins from about 8 graphs a bucket up to bucket 256 and from
    ``bucket_n / 32`` graphs above it.  Both paths give the same arrays."""
    return ("batched" if batch >= max(PACK_BATCHED_MIN, bucket_n // 32)
            else "per_graph")


def _fill_children(child_mat: np.ndarray, child: np.ndarray,
                   parent: np.ndarray) -> None:
    """Scatter each edge into its parent's row of ``child_mat``, children
    in ascending order (the order the device repair walks them); an
    out-degree above the width raises :meth:`CompGraph.child_matrix`'s
    ``ValueError``."""
    _, N, width = child_mat.shape
    order = np.argsort(parent, kind="stable")
    keys = parent[order]
    rank = np.arange(len(keys)) - np.searchsorted(keys, keys)
    over = np.flatnonzero(rank >= width)
    if len(over):
        u = keys[over[0]]
        raise ValueError(f"node {u % N} has out-degree "
                         f"{np.count_nonzero(keys == u)} > {width}")
    child_mat.reshape(-1, width)[keys, rank] = child[order] % N


def _node_block(values, shape, slots, dtype) -> np.ndarray:
    """Per-graph node arrays scattered into one zero-padded block."""
    out = np.zeros(shape, dtype=dtype)
    out.reshape(-1)[slots] = np.concatenate(values)
    return out


def pack_padded(
    graphs: list[CompGraph],
    bucket_n: int | None = None,
    max_deg: int = 6,
    min_bucket: int = MIN_BUCKET,
    child_width: int | None = None,
    decode_only: bool = False,
    labels: tuple[list, list] | None = None,
) -> PaddedGraphBatch:
    """Embed + pad a list of graphs to a common ``bucket_n`` node count.

    ``decode_only`` skips the repair-side structures — the O(n^2) ancestor
    closure and the child matrix become zero-width placeholders — for
    callers that only run the decode (``greedy_orders``); the fused
    schedule path packs everything.

    ``labels`` (optional) is the ``(assigns, orders)`` pair from
    :func:`repro.core.rl.label_graphs` — per-graph arrays of length ``g.n``
    that are zero padded into the batch's ``label_assign``/``label_order``
    fields, turning the serving pack into a training pack.

    The whole batch is built with array operations over flat per-node and
    per-edge arrays; only the levels and the ancestor closure depend on
    :func:`pack_path`, and both paths give the same arrays."""
    if not graphs:
        raise ValueError("empty graph list")
    n_max = max(g.n for g in graphs)
    if bucket_n is None:
        bucket_n = bucket_for(n_max, min_bucket)
    if n_max > bucket_n:
        raise ValueError(f"graph with {n_max} nodes exceeds bucket {bucket_n}")
    B = len(graphs)
    levels_of, closure_of = PACK_PATHS[pack_path(bucket_n, B)]
    ns = np.fromiter((g.n for g in graphs), np.int64, B)
    slots = node_slots(ns, bucket_n)
    shape = (B, bucket_n)
    la = lo = None
    with TraceAnnotation("respect.pack.embed"):
        pmat, child, parent = flat_parents(graphs, slots, bucket_n, max_deg)
        if child_width is None:
            child_width = 0 if decode_only else _child_width_for(
                int(np.bincount(parent).max(initial=0)))
        cmat = np.full((B, bucket_n, child_width), -1, dtype=np.int32)
        if not decode_only:
            _fill_children(cmat, child, parent)
        flops, param_bytes, out_bytes = (
            _node_block([getattr(g, a) for g in graphs], shape, slots,
                        np.float32)
            for a in ("flops", "param_bytes", "out_bytes"))
        mem = _node_block([g.param_bytes + g.out_bytes for g in graphs],
                          shape, slots, np.float64)
        feats = embed_rows(levels_of(graphs, pmat, slots),
                           op_id_block(graphs, bucket_n, slots), mem, pmat,
                           ns)
        if labels is not None:
            la = _node_block(labels[0], shape, slots, np.int32)
            lo = _node_block(labels[1], shape, slots, np.int32)
    # the O(n^2) ancestor closure in a pass of its own, so a trace times
    # it apart from the embedding
    if decode_only:
        amat = np.zeros((B, 0, 0), dtype=bool)
    else:
        with TraceAnnotation("respect.pack.closure"):
            amat = closure_of(graphs, pmat, slots)
    with TraceAnnotation("respect.pack.h2d"):
        return PaddedGraphBatch(
            feats=jnp.asarray(feats),
            parent_mat=jnp.asarray(pmat),
            child_mat=jnp.asarray(cmat),
            ancestor_mat=jnp.asarray(amat),
            flops=jnp.asarray(flops),
            param_bytes=jnp.asarray(param_bytes),
            out_bytes=jnp.asarray(out_bytes),
            n_valid=jnp.asarray(ns.astype(np.int32)),
            label_assign=None if la is None else jnp.asarray(la),
            label_order=None if lo is None else jnp.asarray(lo),
            dense=bool((ns == bucket_n).all()),
        )


class _LRU:
    """Tiny LRU keyed cache (compiled decode fns are the values).

    Thread-safe: a lock guards every OrderedDict mutation so the decoder
    can be shared between the serving worker and direct callers.  Two
    threads racing to compile the same missing key both compile and the
    second ``put`` replaces the first — wasted work, never corruption.
    """

    def __init__(self, maxsize: int):
        self.maxsize = maxsize
        self._d: OrderedDict = OrderedDict()
        self._lock = threading.Lock()
        self.inserted = 0      # puts, each a program built
        self.evicted = 0

    def get(self, key):
        with self._lock:
            if key not in self._d:
                return None
            self._d.move_to_end(key)
            return self._d[key]

    def put(self, key, value):
        with self._lock:
            self._d[key] = value
            self._d.move_to_end(key)
            self.inserted += 1
            while len(self._d) > self.maxsize:
                self._d.popitem(last=False)
                self.evicted += 1

    def keys(self) -> list:
        with self._lock:
            return list(self._d.keys())

    def __len__(self):
        with self._lock:
            return len(self._d)

    def __contains__(self, key):
        with self._lock:
            return key in self._d


class BucketedDecoder:
    """Run many graphs through shape-bucketed jitted programs.

    One instance owns the LRU of compiled per-shape programs;
    `RespectScheduler` holds one for its lifetime so repeated
    `schedule_many` calls hit warm programs.  ``logits_impl`` selects the
    pointer/glimpse op for decode steps: None auto-picks the Pallas kernel
    on TPU and the hoisted pure-jnp path elsewhere; "ref"/"interpret"/
    "pallas" force a :mod:`repro.kernels.ptr` implementation.

    ``decode_impl`` selects how the WHOLE pointing loop runs (see
    :data:`DECODE_IMPLS`): "scan" keeps the per-step ``lax.scan``
    (unrolled by :data:`DECODE_UNROLL`), "kernel" runs the persistent
    whole-decode Pallas kernel (:mod:`repro.kernels.ptr.decode` — TPU),
    "kernel-interpret" the same kernel through the Pallas interpreter
    (CPU-testable), and None auto-picks per bucket: the kernel on TPU
    when :func:`repro.kernels.ptr.ops.decode_kernel_supported` accepts
    the (bucket, hidden) shape and the system is uniform, the scan
    everywhere else.  A forced "kernel" on a backend, shape or
    heterogeneous system it cannot run raises ``ValueError``.
    The ``RESPECT_DECODE_IMPL`` env var overrides the default when no
    explicit argument is given.
    ``decode_bf16`` stores the kernel's context/projection blocks in
    bfloat16 (f32 accumulation; kernel paths only, default off).
    """

    def __init__(self, mask_infeasible: bool = True, max_deg: int = 6,
                 min_bucket: int = MIN_BUCKET, max_compiled: int = 16,
                 logits_impl: str | None = None,
                 decode_impl: str | None = None,
                 decode_bf16: bool = False):
        self.mask_infeasible = mask_infeasible
        self.max_deg = max_deg
        self.min_bucket = min_bucket
        self.logits_impl = logits_impl
        if decode_impl is None:
            decode_impl = os.environ.get(DECODE_IMPL_ENV) or None
        if decode_impl not in DECODE_IMPLS:
            raise ValueError(
                f"decode_impl {decode_impl!r} not one of {DECODE_IMPLS}")
        self.decode_impl = decode_impl
        self.decode_bf16 = decode_bf16
        self._fns = _LRU(max_compiled)

    # ------------------------------------------------------------------ #
    def _logits_builder(self):
        impl = self.logits_impl
        if impl is None and jax.default_backend() == "tpu":
            impl = "pallas"
        if impl is None:
            return None
        from ..kernels.ptr import ops as ptr_ops
        return lambda params, C: ptr_ops.make_logits_fn(params, C, impl=impl)

    def _resolve_decode_impl(self, bucket_n: int, hidden: int,
                             conditioned: bool = False) -> str:
        """Pick the decode impl for one compiled shape (see class doc).

        ``conditioned`` marks a profile-conditioned decode (heterogeneous /
        capacity-constrained system): the whole-decode kernel has no system
        input, so auto runs those programs on the scan path and a forced
        kernel raises ``ValueError``.
        """
        from ..kernels.ptr import ops as ptr_ops
        if conditioned:
            if self.decode_impl in ("kernel", "kernel-interpret"):
                raise ValueError(
                    f"decode_impl={self.decode_impl!r} cannot run a profile-"
                    "conditioned (heterogeneous or memory-capped) system: "
                    "the whole-decode kernel has no system input; leave "
                    "decode_impl unset to pick the scan for it")
            return "scan"
        impl = self.decode_impl
        if impl is None:
            if (jax.default_backend() == "tpu"
                    and ptr_ops.decode_kernel_supported(bucket_n, hidden)):
                return "kernel"
            return "scan"
        if impl == "kernel":
            if jax.default_backend() != "tpu":
                raise ValueError(
                    f"decode_impl='kernel' is the compiled TPU kernel "
                    f"(backend={jax.default_backend()}); use "
                    "'kernel-interpret' to run the kernel here")
            if not ptr_ops.decode_kernel_supported(bucket_n, hidden):
                raise ValueError(
                    f"decode_impl='kernel' cannot run bucket_n={bucket_n}, "
                    f"hidden={hidden}: the blocks do not tile or fit VMEM; "
                    "leave decode_impl unset to pick the scan for it")
        return impl

    @staticmethod
    def _hidden_of(params) -> int:
        return int(params["dec0"].shape[-1])

    def _decode_fn(self, bucket_n: int, bucket_b: int, impl: str):
        key = ("decode", bucket_n, bucket_b, impl)
        fn = self._fns.get(key)
        if fn is None:
            mask_infeasible = self.mask_infeasible
            if impl in ("kernel", "kernel-interpret"):
                from ..kernels.ptr import decode as ptr_decode
                interpret = impl == "kernel-interpret"
                bf16 = self.decode_bf16

                def batched(params, feats, pmat, n_valid):
                    order, _, _ = ptr_decode.decode_pack(
                        params, feats, pmat, n_valid,
                        mask_infeasible=mask_infeasible,
                        interpret=interpret, bf16=bf16)
                    return order
            else:
                builder = self._logits_builder()

                def batched(params, feats, pmat, n_valid):
                    def one(f, p, nv):
                        order, _, _ = ptrnet.greedy_order(
                            params, f, p, mask_infeasible, nv, builder,
                            unroll=DECODE_UNROLL)
                        return order

                    return jax.vmap(one)(feats, pmat, n_valid)

            fn = jax.jit(batched)
            self._fns.put(key, fn)
        return fn

    def _fused_fn(self, bucket_n: int, bucket_b: int, child_width: int,
                  n_stages: int, system: PipelineSystem, impl: str):
        key = ("fused", bucket_n, bucket_b, child_width, n_stages, system,
               impl)
        fn = self._fns.get(key)
        if fn is None:
            mask_infeasible = self.mask_infeasible
            # Static per-program system inputs.  Uniform systems yield
            # sys_feat=None and caps=None, so the traced program — and the
            # compiled executable a given (shape, system) key maps to — is
            # unchanged from the pre-vector engine.
            profile = system.profile_features()
            sys_feat = jnp.asarray(profile) if profile.any() else None
            caps = system.capacity_vector()

            def post_one(order, p, c, a, fl, pb, ob, nv):
                assign, _ = segment.rho_dp_jax(
                    order, fl, pb, ob, p, n_stages, system, n_valid=nv)
                return segment.repair_jax(p, c, a, assign, n_stages,
                                          param_bytes=pb, mem_capacity=caps)

            if impl in ("kernel", "kernel-interpret"):
                if sys_feat is not None:
                    raise ValueError(
                        "whole-decode kernel cannot run a profile-"
                        "conditioned system; resolve the impl with "
                        "conditioned=True (scan)")
                from ..kernels.ptr import decode as ptr_decode
                interpret = impl == "kernel-interpret"
                bf16 = self.decode_bf16

                def batched(params, batch: PaddedGraphBatch):
                    orders, _, _ = ptr_decode.decode_pack(
                        params, batch.feats, batch.parent_mat,
                        batch.n_valid, mask_infeasible=mask_infeasible,
                        interpret=interpret, bf16=bf16)
                    assigns = jax.vmap(post_one)(
                        orders, batch.parent_mat, batch.child_mat,
                        batch.ancestor_mat, batch.flops,
                        batch.param_bytes, batch.out_bytes, batch.n_valid)
                    return orders, assigns
            else:
                builder = self._logits_builder()

                def batched(params, batch: PaddedGraphBatch):
                    def one(f, p, c, a, fl, pb, ob, nv):
                        order, _, _ = ptrnet.greedy_order(
                            params, f, p, mask_infeasible, nv, builder,
                            unroll=DECODE_UNROLL, sys_feat=sys_feat)
                        return order, post_one(order, p, c, a, fl, pb, ob,
                                               nv)

                    return jax.vmap(one)(
                        batch.feats, batch.parent_mat, batch.child_mat,
                        batch.ancestor_mat, batch.flops, batch.param_bytes,
                        batch.out_bytes, batch.n_valid)

            fn = jax.jit(batched)
            self._fns.put(key, fn)
        return fn

    @property
    def compiled_shapes(self) -> list[tuple]:
        return [k[1:] for k in self._fns.keys()]

    @property
    def programs_built(self) -> int:
        """Programs put in the LRU: each compiles on its first call."""
        return self._fns.inserted

    @property
    def programs_evicted(self) -> int:
        """Programs the LRU dropped; a later call of that shape rebuilds."""
        return self._fns.evicted

    # ------------------------------------------------------------------ #
    def _packed_buckets(self, graphs: list[CompGraph],
                        decode_only: bool = False):
        """Yield (bucket_n, idxs, batch) with both dims padded to buckets."""
        for bucket_n, idxs in bucketize(graphs, self.min_bucket).items():
            with TraceAnnotation("respect.pack", bucket_n=bucket_n,
                                 batch=len(idxs),
                                 path=pack_path(bucket_n, len(idxs))):
                batch = pack_padded(
                    [graphs[i] for i in idxs], bucket_n, self.max_deg,
                    decode_only=decode_only)
                bucket_b = 1 << (batch.batch - 1).bit_length()
                batch = batch.pad_batch(bucket_b)
            yield bucket_n, idxs, batch

    def greedy_orders(self, params, graphs: list[CompGraph]) -> list[np.ndarray]:
        """Decode every graph; returns per-graph orders (length ``g.n``).

        Decode-only path — kept for callers that want raw orders (training
        eval, benchmarks measuring the decode/post split); serving uses
        :meth:`fused_schedules`.
        """
        orders: list[np.ndarray | None] = [None] * len(graphs)
        hidden = self._hidden_of(params)
        for _, idxs, batch in self._packed_buckets(graphs, decode_only=True):
            impl = self._resolve_decode_impl(batch.bucket_n, hidden)
            out = self._decode_fn(batch.bucket_n, batch.batch, impl)(
                params, batch.feats, batch.parent_mat, batch.n_valid)
            out = np.asarray(out)
            for row, i in enumerate(idxs):
                orders[i] = out[row, : graphs[i].n].astype(np.int64)
        return orders

    def fused_schedules(
        self,
        params,
        graphs: list[CompGraph],
        n_stages: int,
        system: PipelineSystem,
    ) -> list[tuple[np.ndarray, np.ndarray]]:
        """Decode + segment + repair every graph on device.

        Returns per-graph ``(order, assignment)`` pairs, positionally
        aligned with ``graphs``; each bucket runs as one jitted vmapped
        XLA program and the host only packs inputs and slices outputs.
        The result is identical to the host pipeline
        ``repair(rho(greedy_order(g)))`` (property-tested).
        """
        system = system.with_stages(n_stages)
        results: list[tuple[np.ndarray, np.ndarray] | None] = [None] * len(graphs)
        hidden = self._hidden_of(params)
        conditioned = bool(system.profile_features().any())
        for _, idxs, batch in self._packed_buckets(graphs):
            impl = self._resolve_decode_impl(batch.bucket_n, hidden,
                                             conditioned=conditioned)
            built = self.programs_built
            fn = self._fused_fn(batch.bucket_n, batch.batch,
                                batch.child_width, n_stages, system, impl)
            with TraceAnnotation("respect.run", bucket_n=batch.bucket_n,
                                 bucket_b=batch.batch, impl=impl,
                                 new_program=self.programs_built != built):
                with TraceAnnotation("respect.dispatch"):
                    orders, assigns = fn(params, batch)
                with TraceAnnotation("respect.fetch"):
                    orders = np.asarray(orders)
                    assigns = np.asarray(assigns)
                with TraceAnnotation("respect.unpack"):
                    for row, i in enumerate(idxs):
                        n = graphs[i].n
                        results[i] = (orders[row, :n].astype(np.int64),
                                      assigns[row, :n].astype(np.int64))
        return results
