"""REINFORCE training for RESPECT (paper §III-B "RL Training").

Reward (Eq. 3): cosine similarity between the stage-assignment vector
``S' = rho(pi)`` produced from the policy's sequence and the exact solver's
``S = rho(gamma)``.  The paper's PyTorch pipeline computes rho and the reward
on the host; here the *entire* step — stochastic decode, rho's segmentation
DP, cosine reward, greedy rollout baseline, policy gradient and the Adam
update — is one jitted XLA program (`train_step`), which is both the TPU-
portable design and orders of magnitude faster per step on this machine.

Gradient (Eq. 6): REINFORCE with a *rollout baseline* b(G) (Kool et al. [7]):
the advantage is R(sample) - R(greedy rollout of the best-so-far policy);
baseline parameters are refreshed from the online policy whenever the online
policy's greedy reward improves on an eval batch (`maybe_update_baseline`).

Batch representation: training consumes the SAME pad-aware
:class:`repro.core.batching.PaddedGraphBatch` the serving engine runs on —
graphs of mixed sizes pad to a power-of-two node bucket, ``n_valid`` marks
the real prefix, and ``label_assign``/``label_order`` carry the exact-solver
supervision.  Every step quantity is masked: the decode emits zero
logp/entropy on padded steps (:mod:`repro.core.ptrnet`), the segmentation DP
is ``n_valid``-generalized (:mod:`repro.core.segment`), stage vectors are
zeroed past ``n_valid`` before the cosine, and inert batch-padding rows
(``n_valid == 0``) carry zero weight in every mean.  Stage vectors are small
integers, so the cosine's sums are exact in f32 — rewards, labels and
exact-match of a padded mixed-size step are *bit-identical* to the per-size
unpadded path (parity-tested).

Scale: ``make_train_step(..., mesh=...)`` runs the step data-parallel via
``shard_map`` over the batch axis — per-device microbatches, psum-reduced
gradient/metric sums normalized by the global valid-graph count, one
replicated parameter update — so the sharded trajectory matches the
single-device trajectory at equal global batch.  ``TrainState`` makes the
whole trainer functional (params, baseline, opt state, step, best baseline
reward), which is what lets :mod:`repro.checkpoint.manager` round-trip it.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
from pathlib import Path
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .. import optim
from . import ptrnet
from .batching import PaddedGraphBatch, bucket_for, pack_padded
from .costmodel import PipelineSystem
from .exact import exact_bb, order_from_assignment
from .graph import CompGraph
from .segment import exact_dp_batch, rho_dp_jax

__all__ = [
    "label_graphs",
    "pack_graphs",
    "rho_dp_jax",
    "cosine_reward",
    "make_rollout_fn",
    "make_train_step",
    "make_eval_fn",
    "TrainState",
    "init_train_state",
    "RLTrainer",
]


# --------------------------------------------------------------------- #
# exact labeling (vmapped pad-aware DP, on-disk cache)
# --------------------------------------------------------------------- #
@functools.lru_cache(maxsize=32)
def _dp_label_fn(n_stages: int, system: PipelineSystem):
    """Jitted vmapped exact-DP labeler
    (:func:`repro.core.segment.exact_dp_batch`): graphs of any
    ``n <= bucket_n`` solve together in ONE program per bucket (identity
    order — node indices are topological by CompGraph
    construction, exactly the order :func:`repro.core.exact.exact_dp`
    segments by default; padded trailing slots are zero-cost, so the valid
    prefix matches the unpadded solve bit-for-bit)."""
    return jax.jit(lambda fl, pb, ob, pmat, nv: exact_dp_batch(
        fl, pb, ob, pmat, n_stages, system, nv))


def _label_cache_key(g: CompGraph, n_stages: int, system: PipelineSystem,
                     method: str, max_deg: int, bb_budget_s: float) -> str:
    h = hashlib.sha256()
    h.update(g.content_hash().encode())
    # bb labels depend on the solver time budget; dp labels don't.
    budget = bb_budget_s if method == "bb" else 0.0
    h.update(repr((n_stages, method, max_deg, budget, system.compute_rate,
                   system.compute_eff, system.link_bw, system.cache_bytes,
                   system.fixed_overhead_s)).encode())
    if system.mem_capacity is not None:
        # appended ONLY when set, so scalar systems keep their pre-capacity
        # on-disk label-cache keys
        h.update(repr(system.mem_capacity).encode())
    return h.hexdigest()[:40]


def label_graphs(
    graphs: list[CompGraph],
    n_stages: int,
    system: PipelineSystem,
    max_deg: int = 6,
    label_method: str = "dp",
    bb_budget_s: float = 0.25,
    cache_dir: str | Path | None = None,
) -> tuple[list[np.ndarray], list[np.ndarray]]:
    """Exact stage labels + imitation orders for a list of graphs.

    ``label_method="dp"`` solves all cache-miss graphs of one size *bucket*
    (mixed sizes included — the DP is pad-aware) in ONE vmapped XLA program
    (:func:`repro.core.segment.rho_dp_jax` over the identity topological
    order — the same contiguous-segmentation DP as :func:`exact_dp`,
    lexicographic tie-break included, in f32), replacing the former
    per-graph host loop.  ``"bb"`` keeps the branch-and-bound host solver
    for arbitrary-DAG exactness.  With ``cache_dir`` each graph's label is
    persisted as a tiny ``.npz`` keyed by content hash, so re-labeling the
    same graphs (e.g. deterministic ``DagSampler`` epochs) never re-solves.
    """
    system = system.with_stages(n_stages)
    la: list[np.ndarray | None] = [None] * len(graphs)
    cache = Path(cache_dir) if cache_dir is not None else None
    keys: list[str | None] = [None] * len(graphs)
    misses: list[int] = []
    for i, g in enumerate(graphs):
        if cache is not None:
            keys[i] = _label_cache_key(
                g, n_stages, system, label_method, max_deg, bb_budget_s)
            p = cache / f"{keys[i]}.npz"
            if p.exists():
                with np.load(p) as d:
                    la[i] = d["assign"].astype(np.int64)
                continue
        misses.append(i)

    if misses:
        if label_method == "bb":
            for i in misses:
                assign, _ = exact_bb(graphs[i], n_stages, system,
                                     time_budget_s=bb_budget_s)
                la[i] = np.asarray(assign, dtype=np.int64)
        else:
            by_bucket: dict[int, list[int]] = {}
            for i in misses:
                by_bucket.setdefault(bucket_for(graphs[i].n), []).append(i)
            for bucket_n, idxs in by_bucket.items():
                B = len(idxs)
                fl = np.zeros((B, bucket_n), np.float32)
                pb = np.zeros((B, bucket_n), np.float32)
                ob = np.zeros((B, bucket_n), np.float32)
                pmat = np.full((B, bucket_n, max_deg), -1, np.int32)
                nv = np.zeros(B, np.int32)
                for row, i in enumerate(idxs):
                    g = graphs[i]
                    fl[row, : g.n] = g.flops
                    pb[row, : g.n] = g.param_bytes
                    ob[row, : g.n] = g.out_bytes
                    pmat[row, : g.n] = g.parent_matrix(max_deg)
                    nv[row] = g.n
                assigns, _ = _dp_label_fn(n_stages, system)(
                    jnp.asarray(fl), jnp.asarray(pb), jnp.asarray(ob),
                    jnp.asarray(pmat), jnp.asarray(nv))
                assigns = np.asarray(assigns, dtype=np.int64)
                for row, i in enumerate(idxs):
                    la[i] = assigns[row, : graphs[i].n]
        if cache is not None:
            cache.mkdir(parents=True, exist_ok=True)
            for i in misses:
                np.savez(cache / f"{keys[i]}.npz", assign=la[i])

    lo = [order_from_assignment(a) for a in la]
    return la, lo


def pack_graphs(
    graphs: list[CompGraph],
    n_stages: int,
    system: PipelineSystem,
    max_deg: int = 6,
    label_method: str = "dp",
    bb_budget_s: float = 0.25,
    cache_dir: str | Path | None = None,
    bucket_n: int | None = None,
    pad: bool = True,
) -> PaddedGraphBatch:
    """Embed + label a list of graphs (mixed sizes allowed) into one labeled
    :class:`PaddedGraphBatch` — the SAME representation serving consumes.

    Labeling runs through :func:`label_graphs` (vmapped pad-aware exact DP
    by default, optional on-disk cache).  Nodes pad to ``bucket_n``
    (default: the power-of-two bucket of the largest graph; ``pad=False``
    packs exactly to the largest graph's size — the unpadded reference the
    parity tests compare against).  Training only needs the decode-side
    structures, so the O(n^2) ancestor closure / child matrix are skipped.
    """
    la, lo = label_graphs(
        graphs, n_stages, system, max_deg=max_deg,
        label_method=label_method, bb_budget_s=bb_budget_s,
        cache_dir=cache_dir)
    if bucket_n is None and not pad:
        bucket_n = max(g.n for g in graphs)
    return pack_padded(graphs, bucket_n=bucket_n, max_deg=max_deg,
                       decode_only=True, labels=(la, lo))


# --------------------------------------------------------------------- #
# rho as a jittable DP: shared with serving — see repro.core.segment.
# rho_dp_jax (imported above) mirrors exact_dp INCLUDING its lexicographic
# (bottleneck, latency) tie-break, so dp labels and rewards resolve ties
# exactly like the host solver.
# --------------------------------------------------------------------- #
def cosine_reward(assign, label_assign, eps: float = 1e-8):
    """Eq. 3: cosine similarity of stage vectors.

    Stage vectors are small integers, so every sum below is exact in f32
    regardless of padding length or reduction order — padded stage vectors
    (zeros past ``n_valid``) score bit-identically to unpadded ones.
    """
    a = assign.astype(jnp.float32)
    b = label_assign.astype(jnp.float32)
    denom = jnp.maximum(jnp.linalg.norm(a) * jnp.linalg.norm(b), eps)
    return jnp.dot(a, b) / denom


# --------------------------------------------------------------------- #
# training / eval steps (all pad-aware)
# --------------------------------------------------------------------- #
def _policy_rewards(params, batch: PaddedGraphBatch, keys, n_stages, system,
                    mask_infeasible, sample: bool):
    """vmapped pad-aware decode + rho + reward over a labeled padded batch.

    ``keys`` is a (B, 2) per-graph key array (split OUTSIDE so the sharded
    step sees the same per-graph streams as the single-device step).
    Returns per-graph (rewards, logp_sum, entropy_mean, orders, assigns);
    padded node slots contribute zero logp/entropy and stage 0, inert
    ``n_valid == 0`` rows score zero reward.
    """

    dense = batch.dense   # static: skip n_valid masking for equal-size packs
    # profile conditioning: uniform systems pass None (no extra ops — the
    # traced program is unchanged), heterogeneous systems add the projected
    # profile to the decoder start token so training sees the hardware.
    profile = system.profile_features()
    sys_feat = jnp.asarray(profile) if profile.any() else None

    def one(feats, pmat, fl, pb, ob, label, nv, k):
        nv_d = None if dense else nv
        if sample:
            order, logp, ent = ptrnet.sample_order(
                params, feats, pmat, k, mask_infeasible, n_valid=nv_d,
                sys_feat=sys_feat)
        else:
            order, logp, ent = ptrnet.greedy_order(
                params, feats, pmat, mask_infeasible, n_valid=nv_d,
                sys_feat=sys_feat)
        assign, _ = rho_dp_jax(order, fl, pb, ob, pmat, n_stages, system,
                               n_valid=nv_d)
        if not dense:
            valid = jnp.arange(assign.shape[0]) < nv
            assign = jnp.where(valid, assign, 0)
        r = cosine_reward(assign, label)
        # padded steps carry exactly zero logp/entropy; normalize entropy
        # by the REAL step count so it matches the unpadded decode's mean.
        ent_mean = ent.sum() / jnp.maximum(nv.astype(jnp.float32), 1.0)
        return r, logp.sum(), ent_mean, order, assign

    return jax.vmap(one)(
        batch.feats, batch.parent_mat, batch.flops, batch.param_bytes,
        batch.out_bytes, batch.label_assign, batch.n_valid, keys,
    )


def make_rollout_fn(n_stages: int, system: PipelineSystem,
                    mask_infeasible: bool = True, sample: bool = False,
                    decode_impl: str | None = None):
    """Jitted per-graph rollout: (params, batch, key) -> (rewards, logp,
    entropy, orders, assigns), each leading-dim B.  The building block the
    train/eval steps share; exposed for parity tests and benchmarks.

    ``decode_impl`` ("kernel" | "kernel-interpret") runs the decode
    through the persistent whole-decode Pallas kernel
    (:mod:`repro.kernels.ptr.decode`) instead of the per-graph scan: the
    sampled variant consumes the same per-step ``fold_in`` uniform
    stream, so rollout trajectories match the scan path.  Rollouts are
    forward-only — the REINFORCE loss (`_sum_loss_fn`) differentiates
    through the sampled log-probs and therefore always keeps the scan.
    """
    system = system.with_stages(n_stages)

    if decode_impl in ("kernel", "kernel-interpret"):
        if system.profile_features().any():
            raise ValueError(
                "whole-decode kernel rollouts cannot condition on a "
                "heterogeneous system profile; use the scan decode_impl")
        from ..kernels.ptr import decode as ptr_decode
        interpret = decode_impl == "kernel-interpret"

        @jax.jit
        def rollout(params, batch: PaddedGraphBatch, key):
            keys = jax.random.split(key, batch.batch)
            order, logp, ent = ptr_decode.decode_pack(
                params, batch.feats, batch.parent_mat, batch.n_valid,
                sample_keys=keys if sample else None, sampled=sample,
                mask_infeasible=mask_infeasible, interpret=interpret)

            def post(o, lp, en, fl, pb, ob, pmat, label, nv):
                assign, _ = rho_dp_jax(o, fl, pb, ob, pmat, n_stages,
                                       system, n_valid=nv)
                valid = jnp.arange(assign.shape[0]) < nv
                assign = jnp.where(valid, assign, 0)
                r = cosine_reward(assign, label)
                ent_mean = en.sum() / jnp.maximum(
                    nv.astype(jnp.float32), 1.0)
                return r, lp.sum(), ent_mean, o, assign

            return jax.vmap(post)(
                order, logp, ent, batch.flops, batch.param_bytes,
                batch.out_bytes, batch.parent_mat, batch.label_assign,
                batch.n_valid)

        return rollout
    if decode_impl not in (None, "scan"):
        raise ValueError(f"unknown decode_impl {decode_impl!r}")

    @jax.jit
    def rollout(params, batch: PaddedGraphBatch, key):
        keys = jax.random.split(key, batch.batch)
        return _policy_rewards(params, batch, keys, n_stages, system,
                               mask_infeasible, sample)

    return rollout


def _sum_loss_fn(params, baseline_params, batch, keys, n_stages, system,
                 mask_infeasible, entropy_coef):
    """Unnormalized (summed) REINFORCE loss + metric sums over one shard.

    Returning sums (not means) is what makes the data-parallel step exact:
    shards psum the sums and the valid-graph count, then normalize once
    globally — identical to the single-device weighted mean.
    """
    r_s, logp, ent, _, _ = _policy_rewards(
        params, batch, keys, n_stages, system, mask_infeasible, sample=True)
    r_b, _, _, _, _ = _policy_rewards(
        jax.lax.stop_gradient(baseline_params), batch, keys, n_stages,
        system, mask_infeasible, sample=False)
    adv = jax.lax.stop_gradient(r_s - r_b)
    w = (batch.n_valid > 0).astype(jnp.float32)   # inert padding rows: 0
    loss_sum = -jnp.sum(adv * logp * w) - entropy_coef * jnp.sum(ent * w)
    sums = {
        "reward_sample": jnp.sum(r_s * w),
        "reward_baseline": jnp.sum(r_b * w),
        "advantage": jnp.sum(adv * w),
        "entropy": jnp.sum(ent * w),
        "n_graphs": jnp.sum(w),
    }
    return loss_sum, sums


def make_train_step(
    n_stages: int,
    system: PipelineSystem,
    optimizer,
    mask_infeasible: bool = True,
    entropy_coef: float = 0.0,
    mesh=None,
    axis_name: str = "data",
):
    """Build the jitted REINFORCE step: (params, baseline_params, opt_state,
    batch, key) -> (params, opt_state, metrics).

    The one jitted fn serves every (bucket_n, B) shape — mixed-size bucketed
    streams recompile per shape and then hit the jit cache.  With ``mesh``
    (a 1-axis data mesh, see :func:`repro.parallel.sharding
    .data_parallel_mesh`) the loss/grad runs under ``shard_map`` over the
    batch axis: each device rolls out its microbatch, gradient and metric
    SUMS are psum-reduced, and the normalization/clip/Adam update happens
    once on replicated values — the global batch must divide the mesh size.
    """
    system = system.with_stages(n_stages)
    loss_args = (n_stages, system, mask_infeasible, entropy_coef)

    def _finish(params, opt_state, loss_sum, sums, grads):
        W = jnp.maximum(sums["n_graphs"], 1.0)
        grads = jax.tree.map(lambda g: g / W, grads)
        grads, gnorm = optim.clip_by_global_norm(grads, 1.0)
        params, opt_state = optimizer.update(grads, opt_state, params)
        metrics = {k: v / W for k, v in sums.items() if k != "n_graphs"}
        metrics.update(loss=loss_sum / W, grad_norm=gnorm,
                       n_graphs=sums["n_graphs"])
        return params, opt_state, metrics

    if mesh is None:

        @jax.jit
        def train_step(params, baseline_params, opt_state, batch, key):
            keys = jax.random.split(key, batch.batch)
            (loss_sum, sums), grads = jax.value_and_grad(
                _sum_loss_fn, has_aux=True)(
                    params, baseline_params, batch, keys, *loss_args)
            return _finish(params, opt_state, loss_sum, sums, grads)

        return train_step

    from jax.sharding import PartitionSpec as P
    n_dev = mesh.shape[axis_name]

    def sharded_grads(params, baseline_params, batch, keys):
        (loss_sum, sums), grads = jax.value_and_grad(
            _sum_loss_fn, has_aux=True)(
                params, baseline_params, batch, keys, *loss_args)
        loss_sum = jax.lax.psum(loss_sum, axis_name)
        sums = jax.lax.psum(sums, axis_name)
        grads = jax.lax.psum(grads, axis_name)
        return loss_sum, sums, grads

    @jax.jit
    def train_step(params, baseline_params, opt_state, batch, key):
        if batch.batch % n_dev:
            raise ValueError(
                f"global batch {batch.batch} not divisible by "
                f"{n_dev} devices on mesh axis {axis_name!r}")
        keys = jax.random.split(key, batch.batch)
        loss_sum, sums, grads = jax.shard_map(
            sharded_grads, mesh=mesh,
            in_specs=(P(), P(), P(axis_name), P(axis_name)),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )(params, baseline_params, batch, keys)
        return _finish(params, opt_state, loss_sum, sums, grads)

    return train_step


def make_eval_fn(n_stages: int, system: PipelineSystem,
                 mask_infeasible: bool = True):
    """Greedy-decode eval over a labeled padded batch: valid-graph-weighted
    mean reward + mean exact-match of the valid stage-vector prefix."""
    system = system.with_stages(n_stages)

    @jax.jit
    def eval_fn(params, batch: PaddedGraphBatch):
        keys = jnp.zeros((batch.batch, 2), jnp.uint32)   # greedy: unused
        r, _, _, _, assigns = _policy_rewards(
            params, batch, keys, n_stages, system, mask_infeasible,
            sample=False)
        valid = batch.valid_mask()
        match = jnp.all(
            jnp.where(valid, assigns == batch.label_assign, True), axis=-1)
        w = (batch.n_valid > 0).astype(jnp.float32)
        W = jnp.maximum(jnp.sum(w), 1.0)
        return {
            "reward_greedy": jnp.sum(r * w) / W,
            "exact_match": jnp.sum(match.astype(jnp.float32) * w) / W,
        }

    return eval_fn


# --------------------------------------------------------------------- #
# functional trainer state + high-level engine
# --------------------------------------------------------------------- #
@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class TrainState:
    """Everything a training run needs to resume, as one pytree — params,
    rollout-baseline params, optimizer state, step counter and the best
    baseline reward seen — so :mod:`repro.checkpoint.manager` round-trips
    the trainer exactly."""

    params: Any
    baseline_params: Any
    opt_state: Any
    step: jnp.ndarray                  # () int32
    best_baseline_reward: jnp.ndarray  # () float32

    def tree_flatten(self):
        return (self.params, self.baseline_params, self.opt_state,
                self.step, self.best_baseline_reward), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def init_train_state(key, feat_dim: int, hidden: int, optimizer) -> TrainState:
    params = ptrnet.init_params(key, feat_dim, hidden)
    return TrainState(
        params=params,
        baseline_params=jax.tree.map(jnp.copy, params),
        opt_state=optimizer.init(params),
        step=jnp.zeros((), jnp.int32),
        best_baseline_reward=jnp.full((), -jnp.inf, jnp.float32),
    )


class RLTrainer:
    """Paper training setup: Adam @ 1e-4, batch 128, rollout baseline.

    A thin stateful shell over :class:`TrainState` + the jitted step fns.
    ``n_devices`` > 1 builds a 1-axis data mesh and runs the step under
    ``shard_map`` (pure data parallelism: per-device microbatches,
    psum-reduced grads, replicated params).  ``save``/``restore`` go
    through :class:`repro.checkpoint.manager.CheckpointManager`.

    Multi-stage training: the pointer policy emits an *order* — only the
    reward (rho of that order vs the exact label at a given stage count)
    depends on ``n_stages`` — so ONE parameter set trains against many
    stage counts.  Pass ``stage_counts=(2, 3, 4, 6, 8)`` and rotate:
    ``train_step(batch, key, n_stages=k)`` builds (and caches) one jitted
    step per k over the same TrainState; the release pipeline uses this
    to train the shipped agent across the whole eval-grid stage range.
    """

    def __init__(
        self,
        n_stages: int = 4,
        system: PipelineSystem | None = None,
        hidden: int = 256,
        lr: float = 1e-4,
        feat_dim: int | None = None,
        mask_infeasible: bool = True,
        entropy_coef: float = 0.0,
        seed: int = 0,
        n_devices: int | None = None,
        stage_counts: tuple[int, ...] | None = None,
    ):
        from .embedding import embed_dim
        self.stage_counts = tuple(stage_counts) if stage_counts else (n_stages,)
        self.n_stages = self.stage_counts[0] if stage_counts else n_stages
        self._base_system = system or PipelineSystem(self.n_stages)
        self.system = self._base_system.with_stages(self.n_stages)
        self.optimizer = optim.adamw(lr=lr)
        self.hidden = hidden
        self.mask_infeasible = mask_infeasible
        self.entropy_coef = entropy_coef
        feat_dim = feat_dim or embed_dim()
        self.mesh = None
        if n_devices is not None and n_devices > 1:
            from ..parallel.sharding import data_parallel_mesh
            self.mesh = data_parallel_mesh(n_devices)
        self.state = init_train_state(
            jax.random.PRNGKey(seed), feat_dim, hidden, self.optimizer)
        # one jitted train/eval fn per stage count, built lazily — every k
        # shares the single TrainState (params, Adam moments, baseline)
        self._train_steps: dict[int, Any] = {}
        self._eval_fns: dict[int, Any] = {}
        self._ckpt_managers: dict = {}

    def _step_fn(self, k: int):
        if k not in self._train_steps:
            self._train_steps[k] = make_train_step(
                k, self._base_system.with_stages(k), self.optimizer,
                self.mask_infeasible, self.entropy_coef, mesh=self.mesh)
        return self._train_steps[k]

    def _eval_fn_for(self, k: int):
        if k not in self._eval_fns:
            self._eval_fns[k] = make_eval_fn(
                k, self._base_system.with_stages(k), self.mask_infeasible)
        return self._eval_fns[k]

    # -- state views ---------------------------------------------------- #
    @property
    def params(self):
        return self.state.params

    @property
    def baseline_params(self):
        return self.state.baseline_params

    @property
    def opt_state(self):
        return self.state.opt_state

    @property
    def step_count(self) -> int:
        return int(self.state.step)

    # -- training ------------------------------------------------------- #
    def train_step(self, batch: PaddedGraphBatch, key,
                   n_stages: int | None = None) -> dict:
        if not batch.has_labels:
            raise ValueError("training batch carries no labels; pack with "
                             "rl.pack_graphs / DagSampler.next_packed_batch")
        params, opt_state, metrics = self._step_fn(n_stages or self.n_stages)(
            self.state.params, self.state.baseline_params,
            self.state.opt_state, batch, key)
        self.state = dataclasses.replace(
            self.state, params=params, opt_state=opt_state,
            step=self.state.step + 1)
        return {k: float(v) for k, v in metrics.items()}

    def evaluate(self, batch: PaddedGraphBatch,
                 n_stages: int | None = None) -> dict:
        fn = self._eval_fn_for(n_stages or self.n_stages)
        return {k: float(v)
                for k, v in fn(self.state.params, batch).items()}

    def consider_baseline(self, reward: float) -> bool:
        """Adopt the online policy as rollout baseline when ``reward``
        (however the caller aggregated it — single-batch greedy reward or
        a multi-stage-count mean) beats the best seen so far."""
        if reward > float(self.state.best_baseline_reward):
            self.state = dataclasses.replace(
                self.state,
                baseline_params=jax.tree.map(jnp.copy, self.state.params),
                best_baseline_reward=jnp.float32(reward))
            return True
        return False

    def maybe_update_baseline(self, eval_batch: PaddedGraphBatch,
                              n_stages: int | None = None) -> bool:
        """Rollout-baseline refresh: adopt the online policy as baseline when
        its greedy reward beats the best seen so far."""
        return self.consider_baseline(
            self.evaluate(eval_batch, n_stages)["reward_greedy"])

    # -- checkpointing -------------------------------------------------- #
    def _manager(self, ckpt_dir: str | Path):
        """ONE CheckpointManager per directory for the trainer's lifetime,
        so async saves serialize (`save` waits on the in-flight write)
        instead of racing a second manager over the same tmp dir."""
        from ..checkpoint import CheckpointManager
        key = str(Path(ckpt_dir))
        if key not in self._ckpt_managers:
            self._ckpt_managers[key] = CheckpointManager(ckpt_dir)
        return self._ckpt_managers[key]

    def save(self, ckpt_dir: str | Path, blocking: bool = True) -> None:
        """Checkpoint the full TrainState via CheckpointManager (atomic,
        retained, resumable)."""
        self._manager(ckpt_dir).save(self.step_count, self.state,
                                     blocking=blocking)

    def restore(self, ckpt_dir: str | Path) -> int | None:
        """Restore the newest complete checkpoint; returns its step (or
        None when the directory holds none)."""
        step, state = self._manager(ckpt_dir).restore_latest(self.state)
        if step is None:
            return None
        self.state = state
        return step
